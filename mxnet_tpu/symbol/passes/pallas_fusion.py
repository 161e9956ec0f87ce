"""The r6 Pallas fusion pass, ported onto the pass framework.

The matcher/rewriter lives unchanged in ``symbol/fusion.py``
(``fuse_symbol``): BN(+ReLU)→1×1-conv subgraphs substitute the
``_FusedBNReLUConv`` Pallas op, with shape-aware tile bail-outs. This
class is its framework adapter: flag resolution stays on the legacy
``MXTPU_PALLAS_FUSION`` env var.

Mesh binds FIRE since round 18: the fused op wraps its pallas_call in
``shard_map`` over the batch axis when a mesh scope is active
(ops/pallas_fused.py ``mesh_scope``), so the custom call is no longer
GSPMD-opaque — the manager measures the SHARDED program's per-device
bytes and gates the rewrite like any other (ROADMAP item 1).
"""
from __future__ import annotations

from .base import GraphPass

__all__ = ["PallasFusionPass"]


class PallasFusionPass(GraphPass):
    name = "pallas_fusion"
    flag = "MXTPU_PALLAS_FUSION"
    mesh_safe = True           # pallas_call shard_maps over the batch
    modes = ("train", "infer", "serving")

    def precheck(self, ctx):
        from .base import embedding_skip_reason, mesh_axis_skip_reason
        return embedding_skip_reason(ctx) or mesh_axis_skip_reason(ctx)

    def apply(self, sym, shapes, ctx):
        from ..fusion import fuse_symbol
        # the program runs in the bind's compute dtype, and the bytes
        # gate compiles its proxy in f32: a site must tile in both
        run = str(ctx.compute_dtype) if ctx.compute_dtype is not None \
            else "float32"
        new_sym, rep = fuse_symbol(sym, shapes,
                                   dtypes=(run,) if run == "float32"
                                   else (run, "float32"))
        return (new_sym if rep["sites"] else None), rep
