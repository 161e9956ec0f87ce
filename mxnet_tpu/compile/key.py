"""Canonical program cache keys.

A compiled XLA program is reusable exactly when everything that fed the
trace is identical: the graph (symbol JSON), the bound shapes/dtypes,
the optimizer configuration (hyperparameters are baked into the fused
step as trace-time constants — only ``lr`` and the step counter ride as
runtime arguments), the mesh/sharding layout, the fusion-pass flag, and
the backend the executable was built for. ``program_key`` folds all of
that into one sha256 digest; the registry and the persistent cache key
on it.

Version strings (jax / jaxlib / mxnet_tpu / entry format) are kept OUT
of the digest and carried alongside as the ``fingerprint``: a version
upgrade must not silently *miss* (that would quietly recompile forever
against a stale file) — it must *detect* the stale entry, warn, and
overwrite it in place. Hardware identity (backend platform, device
kind, device count) IS part of the digest: a CPU-proxy run and a TPU
run sharing one cache directory are different programs, not stale
versions of each other.
"""
from __future__ import annotations

import hashlib
import json

__all__ = ["ProgramKey", "program_key", "fingerprint", "arg_signature",
           "optimizer_fingerprint", "mesh_fingerprint", "symbol_digest"]

# bump when the on-disk entry layout or the key material schema changes
FORMAT_VERSION = 2

# optimizer attributes that do NOT feed the trace and so must stay OUT
# of the key: the step counter and the base learning rate are runtime
# ARGUMENTS of the fused program (module/fused.py step_fn takes t and
# lr). Hashing them would make a resumed process — restarting mid
# lr-schedule, or simply further along — silently miss every warm
# entry, the exact failure the cache exists to prevent.
_OPT_MUTABLE = {"num_update", "begin_num_update", "_index_update_count",
                "lr"}

_fingerprint_cache = [None]


def fingerprint():
    """Version fingerprint stored WITH each cache entry (not hashed into
    the key): jax/jaxlib/mxnet_tpu versions + entry format. A mismatch
    on load is the version-stale signal."""
    if _fingerprint_cache[0] is None:
        import jax
        try:
            import jaxlib
            jaxlib_v = getattr(jaxlib, "__version__", "?")
        except Exception:
            jaxlib_v = "?"
        from .. import __version__ as mxtpu_v
        _fingerprint_cache[0] = (
            f"jax={jax.__version__};jaxlib={jaxlib_v};"
            f"mxtpu={mxtpu_v};fmt={FORMAT_VERSION}")
    return _fingerprint_cache[0]


def _backend_identity():
    """Hardware identity hashed INTO the key (a different chip is a
    different program, not a stale one)."""
    import jax
    try:
        devs = jax.devices()
        kind = getattr(devs[0], "device_kind", "?")
        return {"platform": jax.default_backend(), "device_kind": kind,
                "ndev": len(devs)}
    except Exception:
        return {"platform": "?", "device_kind": "?", "ndev": 0}


def symbol_digest(symbol):
    """sha256 of the symbol's canonical JSON serialization — the graph
    identity half of every key (MXNet symbols rebuild deterministically
    from JSON, so equal JSON means equal traced graph)."""
    js = symbol.tojson()
    return hashlib.sha256(js.encode("utf-8")).hexdigest()


def arg_signature(args):
    """Structural signature of a concrete argument pytree: a tuple of
    (shape, dtype) per array leaf. The retrace guard stores this per
    entry point and reports the diverging signature when a program
    retraces."""
    import jax
    sig = []
    for leaf in jax.tree_util.tree_leaves(args):
        shape = getattr(leaf, "shape", None)
        if shape is not None:
            sig.append((tuple(int(d) for d in shape),
                        str(getattr(leaf, "dtype", "?"))))
    return tuple(sig)


def optimizer_fingerprint(optimizer):
    """Key material for an optimizer: type name plus every scalar
    hyperparameter and the per-name multiplier dicts. Hyperparameters
    (momentum, wd, betas, lr_mult/wd_mult...) are baked into the fused
    program as constants, so any change is a different program; the
    mutable step counters are runtime args and are excluded."""
    if optimizer is None:
        return None
    out = {"type": type(optimizer).__name__.lower()}
    for k, v in sorted(vars(optimizer).items()):
        if k in _OPT_MUTABLE:
            continue
        if isinstance(v, (int, float, bool, str)):
            out[k] = v
        elif isinstance(v, dict) and k in ("lr_mult", "wd_mult",
                                           "idx2name"):
            out[k] = sorted((str(a), b) for a, b in v.items()
                            if isinstance(b, (int, float, bool, str)))
    return out


def mesh_fingerprint(mesh):
    """Key material for a device mesh: axis names, axis sizes, and the
    device ids in mesh order (GSPMD partitions differently for any of
    these changing)."""
    if mesh is None:
        return None
    try:
        return {
            "axes": list(getattr(mesh, "axis_names", ())),
            "shape": [int(s) for s in
                      getattr(mesh.devices, "shape", ())],
            "devices": [int(getattr(d, "id", -1))
                        for d in mesh.devices.flat],
        }
    except Exception:
        return {"repr": repr(mesh)}


class ProgramKey:
    """One canonical program identity: ``digest`` (sha256 hex over the
    key materials), ``name`` (human label for reports), ``kind`` (entry
    point family), and the ``materials`` dict itself (kept for the
    retrace guard's what-changed diffs)."""

    __slots__ = ("kind", "name", "digest", "materials")

    def __init__(self, kind, name, digest, materials):
        self.kind = kind
        self.name = name
        self.digest = digest
        self.materials = materials

    @property
    def short(self):
        return self.digest[:10]

    def diff(self, other):
        """Names of top-level key materials that differ from ``other``
        (the retrace guard's 'why did this recompile' answer)."""
        if other is None:
            return []
        a, b = self.materials, other.materials
        keys = set(a) | set(b)
        return sorted(k for k in keys if a.get(k) != b.get(k))

    def __repr__(self):
        return f"ProgramKey({self.kind}:{self.name}@{self.short})"


def _canon(obj):
    """Canonicalize key material for json hashing (tuples -> lists,
    dtypes -> str)."""
    if isinstance(obj, dict):
        return {str(k): _canon(v) for k, v in sorted(obj.items())}
    if isinstance(obj, (list, tuple)):
        return [_canon(v) for v in obj]
    if isinstance(obj, (int, float, bool, str)) or obj is None:
        return obj
    return str(obj)


def program_key(kind, name, *, symbol=None, symbol_sha=None,
                input_sigs=(), optimizer=None, mesh=None, fusion=None,
                passes=None, partition=None, extra=None):
    """Build the canonical :class:`ProgramKey` for one entry point.

    ``input_sigs`` is any structural signature of the runtime inputs
    (shapes/dtypes); ``fusion`` the resolved fusion-flag material;
    ``passes`` the rewrite-pipeline fingerprint (per-pass flag/status/
    site count from symbol/passes/manager.py — cached executables must
    never mix pass regimes); ``partition`` the parameter-partition-rule
    fingerprint (parallel/partition.py ``rules_fingerprint`` — two
    processes resolving different layouts trace different programs;
    None when the feature is off keeps keys byte-identical with
    pre-partition builds); ``extra`` entry-point-specific trace inputs
    (guard flag, compute dtype, metric slot signatures, compiler
    options...). Either ``symbol`` or a precomputed ``symbol_sha``
    identifies the graph.
    """
    if symbol_sha is None and symbol is not None:
        symbol_sha = symbol_digest(symbol)
    materials = {
        "kind": kind,
        "symbol": symbol_sha,
        "inputs": _canon(input_sigs),
        "optimizer": _canon(optimizer_fingerprint(optimizer)
                            if optimizer is not None and
                            not isinstance(optimizer, dict) else optimizer),
        "mesh": _canon(mesh_fingerprint(mesh)
                       if mesh is not None and
                       not isinstance(mesh, dict) else mesh),
        "fusion": _canon(fusion),
        "passes": _canon(passes),
        "backend": _backend_identity(),
        "extra": _canon(extra or {}),
    }
    if partition is not None:
        materials["partition"] = _canon(partition)
    blob = json.dumps(materials, sort_keys=True).encode("utf-8")
    digest = hashlib.sha256(blob).hexdigest()
    return ProgramKey(kind, name, digest, materials)
