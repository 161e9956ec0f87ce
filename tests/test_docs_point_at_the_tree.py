"""The documents and the comments name files that exist.

A deletion is finished when nothing still sends a reader to what went.
One case per document (README, the verify skill, every ``docs/**/*.md``)
and one over the comments and docstrings of the program, its tools and
its examples. Two rules, both over plain text:

- a token that looks like a path of this repo —
  ``(tools|mxnet_tpu|tests|benchmark|examples|docs|native)/….(py|md|json|cc|sh)``
  — names a file in the tree;
- a bare ``name.py`` names a file somewhere in the tree (``bench_lstm.py``
  under ``examples/``, ``chip_smoke.py`` at the root).

The reference's own files are cited with their path in the reference
(``python/mxnet/module/executor_group.py``; ``incubator-mxnet/tests/…``
where the first directory is also one of ours), a placeholder as
``<your_script>.py``: neither is a token of the rules above. ROADMAP.md,
PERF.md and CHANGES.md are histories and may name what is gone.
"""
import ast
import io
import pathlib
import re
import tokenize

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
_NOT_INSIDE = r"(?<![\w/.\-<{*])"
REPO_PATH = re.compile(
    _NOT_INSIDE + r"((?:tools|mxnet_tpu|tests|benchmark|examples|docs|native)"
    r"/[\w./\-]*?\.(?:py|md|json|cc|sh))(?![\w\-])")
BARE_PY = re.compile(_NOT_INSIDE + r"([A-Za-z_]\w*\.py)(?![\w\-])")

DOCUMENTS = ["README.md", ".claude/skills/verify/SKILL.md"] + sorted(
    str(p.relative_to(ROOT)) for p in (ROOT / "docs").rglob("*.md"))
SOURCES = "comments and docstrings"


def _python_files():
    for top in ("mxnet_tpu", "examples"):
        yield from sorted((ROOT / top).rglob("*.py"))
    yield from sorted((ROOT / "tools").glob("*.py"))
    yield ROOT / "chip_smoke.py"
    yield ROOT / "__graft_entry__.py"


def _comments_and_docstrings(path):
    text = path.read_text()
    out = [t.string for t in tokenize.generate_tokens(
        io.StringIO(text).readline) if t.type == tokenize.COMMENT]
    for node in ast.walk(ast.parse(text)):
        if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef,
                             ast.AsyncFunctionDef)):
            out.append(ast.get_docstring(node, clean=False) or "")
    return "\n".join(out)


def _dangling(text, basenames):
    gone = {m.group(1) for m in REPO_PATH.finditer(text)
            if not (ROOT / m.group(1)).is_file()}
    gone |= {m.group(1) for m in BARE_PY.finditer(text)
             if m.group(1) not in basenames}
    return sorted(gone)


@pytest.fixture(scope="module")
def basenames():
    skip = {".git", "chiprun_out", ".chip_tree", ".bench_tree",
            "__pycache__"}
    return {p.name for p in ROOT.rglob("*.py")
            if not skip.intersection(p.relative_to(ROOT).parts)}


@pytest.mark.parametrize("where", DOCUMENTS + [SOURCES])
def test_every_path_named_is_a_file_of_the_tree(where, basenames):
    if where == SOURCES:
        gone = {str(p.relative_to(ROOT)): _dangling(
            _comments_and_docstrings(p), basenames)
            for p in _python_files()}
        gone = {k: v for k, v in gone.items() if v}
    else:
        gone = _dangling((ROOT / where).read_text(), basenames)
    assert not gone, (
        f"{where} names files that are not in the tree: {gone}. Correct "
        "the pointer, or delete the sentence that was only a pointer; a "
        "file of the reference is cited with its path there "
        "(python/mxnet/…, incubator-mxnet/tests/…), a placeholder as "
        "<name>.py")
