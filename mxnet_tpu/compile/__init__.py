"""AOT compile & persistent program-cache subsystem.

MXNet's symbolic path made compiled-graph reuse first-class: bucketing
executors share plans, symbols serialize to JSON and rebuild
deterministically. This package is the TPU-native descendant — compiled
XLA programs become durable, keyed, reusable artifacts (the TVM lesson),
and compilation itself a measured, managed stage:

- :mod:`.key` — canonical program identity: sha256 over (symbol JSON,
  input shapes/dtypes, optimizer config, mesh/sharding, fusion flag,
  backend identity), with jax/jaxlib/mxnet_tpu versions carried as a
  separate staleness fingerprint.
- :mod:`.cache` — the persistent cache under ``MXTPU_COMPILE_CACHE_DIR``:
  one CRC-guarded ``.mxprog`` file per program holding the serialized
  executable; corrupt or version-stale entries are detected and rejected
  loudly (never a wrong program), then overwritten by the fresh compile.
- :mod:`.registry` — per-program compile wall time, cache hit/miss
  counters, the retrace guard (what recompiled and which argument
  signature diverged), ``compile::`` profiler spans, and the
  ``load_or_compile`` / ``shared_programs`` entry points the fused
  Module step, ``serving.Predictor``, and ``Executor`` route through.

With a populated cache, a second process running the same fused train
step and Predictor bucket set performs ZERO fresh XLA compiles — crash
auto-resume and serving restarts go from compile storm to file loads
(``mx.compile_report()["totals"]["fresh_compiles"] == 0``, pinned in
tests/test_compile_cache.py).

Inspect with ``mx.compile_report()``; manage the cache directory with
``tools/compile_cache.py`` (``ls`` / ``verify`` / ``prune``).
"""
from __future__ import annotations

from .key import (ProgramKey, program_key, fingerprint, arg_signature,
                  optimizer_fingerprint, mesh_fingerprint, symbol_digest)
from .cache import (PersistentCache, CacheEntryError, default_cache,
                    cache_enabled, wire_jax_cache)
from .registry import (ProgramRecord, JitProgram, load_or_compile,
                       shared_programs, guarded_loaded_program,
                       note_entry_point, get_record, compile_report,
                       donation_supported, reset)

__all__ = [
    "ProgramKey", "program_key", "fingerprint", "arg_signature",
    "optimizer_fingerprint", "mesh_fingerprint", "symbol_digest",
    "PersistentCache", "CacheEntryError", "default_cache",
    "cache_enabled", "wire_jax_cache",
    "ProgramRecord", "JitProgram", "load_or_compile", "shared_programs",
    "guarded_loaded_program", "note_entry_point", "get_record",
    "compile_report", "donation_supported", "reset",
]
