"""Graph-rewrite fusion pass: BN(+ReLU)→1×1-conv onto the Pallas kernel.

XLA cannot fuse across the BatchNorm statistics barrier, so every
batch-norm'd activation of the training step is written and read once
more than the arithmetic needs; the 1×1 convolutions could absorb their
BN/ReLU prologues the way the reference's cuDNN kernels do. This pass
is the graph-level
integration of the verified Pallas kernel (ops/pallas_fused.py): it
pattern-matches

    BatchNorm → Activation(act_type=relu) → Convolution(1×1, stride 1,
    pad 0, dilate 1, groups 1, NCHW)

and the bare ``BatchNorm → 1×1 Convolution`` variant in a bound symbol
graph and substitutes the internal ``_FusedBNReLUConv`` op — the classic
fusion-to-cut-memory-traffic move of TVM (Chen et al., 2018) and the XLA
operator-fusion analysis (Snider & Liang, 2023), applied where XLA
itself cannot.

Match rules (each failure bails that site, recorded in the report):

- conv kernel (1,1), stride (1,1), pad (0,0), dilate (1,1), num_group 1,
  layout NCHW, 4-D data;
- the BN (and ReLU, when present) intermediate is consumed ONLY by the
  next node in the pattern and is not a graph output — other consumers
  would need the materialized tensor anyway;
- BN axis is 1 (channel) and its batch-stat outputs have no graph
  consumers (the running-aux fold reads them through the walker, not
  through graph edges);
- shapes are known and the site has a (bo, bs) output block Mosaic
  accepts in every dtype the program will run in (select_conv_tiles:
  lane/sublane rules and the VMEM budget) — the bail-out happens here,
  by name and reason, never as a compile error.

The rewrite is non-destructive: it returns a NEW graph sharing
unaffected nodes (same uids, so per-node RNG salts stay aligned with
the original), with identical argument/auxiliary name order — the
executors keep the original symbol for naming/serialization and use the
fused one only to build their compiled functions. BN semantics are
preserved exactly: the fused op computes per-batch statistics and
mirrors BatchNorm's input/output layout so the running-aux updates
still fold (Symbol._bn_aux_updates).

Enabled by the ``MXTPU_PALLAS_FUSION`` env flag (mxnet_tpu/config.py):
``1``/``0`` force, ``auto`` (default) = on for TPU backends, off
elsewhere. ``fusion_report()`` (exported as ``mxnet_tpu.fusion_report``)
says what the pass did.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from .. import config
from ..ops.registry import parse_attr
from ..ops.pallas_fused import conv_tile_failure, select_conv_tiles
from .symbol import Symbol, Group, _Node

__all__ = ["fuse_symbol", "maybe_fuse", "fusion_enabled", "fusion_report"]


def fusion_enabled() -> bool:
    """Resolve the MXTPU_PALLAS_FUSION flag: 1/0 force on/off, ``auto``
    (the default) enables the pass only when the default JAX backend is
    a TPU — off-TPU the kernel runs in interpret mode, correct but slow,
    so CPU runs must opt in explicitly (tests do)."""
    v = str(config.get("MXTPU_PALLAS_FUSION", "auto")).strip().lower()
    if v in ("1", "true", "yes", "on"):
        return True
    if v in ("0", "false", "no", "off", ""):
        return False
    import jax
    return jax.default_backend() == "tpu"


def _collect(reset: bool = False) -> dict:
    """What the fusion pass rewrote in this process: per-rewrite site
    lists (conv/bn/activation node names + matmul geometry and tiles)
    and per-site bail-out reasons. One entry per executor build;
    ``by_tag`` splits the site counts by which program was rewritten
    (``executor`` = train/grad builds, ``executor_infer`` = inference-
    only executor binds, ``fused_step`` = the whole-step train program,
    ``predictor`` = serving predict programs).

    Since round 12 this is a filtered VIEW of the pass framework's
    record store (symbol/passes/manager.py — the same records back
    ``pass_report()``); the payload shape and ``by_tag`` keys are
    unchanged."""
    from .passes.manager import collect_fusion
    return collect_fusion(reset)


from ..telemetry import registry as _treg  # noqa: E402

fusion_report = _treg.collector_view("fusion", _collect)


def _record(report: dict):
    """Register a standalone ``maybe_fuse`` rewrite in the shared pass
    record store (the pipeline's own runs record through the manager)."""
    from .passes.manager import record_legacy_fusion
    tag = report.get("tag", "?")
    status = "applied" if report.get("sites") else "no_match"
    record_legacy_fusion(tag, report, status)


def _attrs(node) -> dict:
    return {k: parse_attr(v) for k, v in node.attrs.items()
            if not k.startswith("__")}


def _norm_tup(v) -> Optional[tuple]:
    if v is None:
        return None
    if isinstance(v, (int, float)):
        return (int(v), int(v))
    return tuple(int(x) for x in v)


def _conv_matches(node, attrs) -> bool:
    """1×1/s1/p0/d1 ungrouped NCHW convolution with plain positional
    inputs (data, weight[, bias])."""
    if node.op not in ("Convolution", "Convolution_v1"):
        return False
    if "__input_names__" in node.attrs:
        return False
    if len(node.inputs) not in (2, 3):
        return False
    return (_norm_tup(attrs.get("kernel")) == (1, 1)
            and _norm_tup(attrs.get("stride")) in (None, (1, 1))
            and _norm_tup(attrs.get("pad")) in (None, (0, 0))
            and _norm_tup(attrs.get("dilate")) in (None, (1, 1))
            and int(attrs.get("num_group", 1) or 1) == 1
            and attrs.get("layout") in (None, "NCHW"))


def fuse_symbol(sym: Symbol, shapes: Dict[str, tuple],
                dtypes=("float32",)) -> Tuple[Symbol, dict]:
    """Rewrite matched BN(+ReLU)→1×1-conv subgraphs of ``sym`` onto the
    fused Pallas op. ``shapes`` maps variable names (arguments AND aux)
    to concrete shapes — executors pass their bound array shapes so the
    tile bail-out is decided here, not mid-trace. ``dtypes`` lists every
    activation dtype the rewritten program will be compiled in, the one
    it runs in first (a bf16 step is also compiled in f32 by the bytes
    gate's proxy): a site needs a legal tile in each of them.

    Returns ``(new_sym, report)``; when nothing matched, ``new_sym`` is
    ``sym`` itself. The report lists rewritten sites and per-site
    bail-out reasons and is NOT registered globally — callers go through
    ``maybe_fuse`` for that."""
    _, node_shapes = sym._propagate_shapes(dict(shapes))
    nodes = sym._topo_nodes()
    heads = {(id(s._node), s._out_index) for s in sym._output_symbols()}
    uses: Dict[tuple, int] = {}
    for n in nodes:
        for p, i in n.inputs:
            uses[(id(p), i)] = uses.get((id(p), i), 0) + 1

    def sole_feed(node, consumer):
        """node's output 0 feeds ONLY ``consumer``, exactly once, and is
        not a graph head."""
        k = (id(node), 0)
        if k in heads or uses.get(k, 0) != 1:
            return False
        return sum(1 for p, i in consumer.inputs
                   if p is node and i == 0) == 1

    sites: Dict[int, dict] = {}      # id(conv node) -> match info
    report = {"sites": [], "bailouts": []}
    claimed = set()                  # ids of bn/relu nodes already matched
    for node in nodes:
        cattrs = _attrs(node)
        if not _conv_matches(node, cattrs):
            continue
        src, src_idx = node.inputs[0]
        if src_idx != 0 or id(src) in claimed:
            continue
        relu = None
        if src.op == "Activation" and \
                _attrs(src).get("act_type", "relu") == "relu":
            relu = src
            bn, bn_idx = relu.inputs[0]
            if bn_idx != 0 or id(bn) in claimed:
                continue
        elif src.op in ("BatchNorm", "BatchNorm_v1"):
            bn = src
        else:
            continue

        def bail(reason):
            report["bailouts"].append({"conv": node.name, "bn": bn.name,
                                       "reason": reason})

        battrs = _attrs(bn)
        if bn.op not in ("BatchNorm", "BatchNorm_v1"):
            continue
        if "__input_names__" in bn.attrs or len(bn.inputs) != 5:
            bail("BatchNorm with non-standard inputs")
            continue
        if int(battrs.get("axis", 1) or 1) != 1:
            bail(f"BatchNorm axis={battrs.get('axis')} (need channel "
                 "axis 1)")
            continue
        if relu is not None and not sole_feed(relu, node):
            bail("activation output has other consumers")
            continue
        if not sole_feed(bn, relu if relu is not None else node):
            bail("BatchNorm output has other consumers")
            continue
        if any(uses.get((id(bn), i), 0) or (id(bn), i) in heads
               for i in (1, 2)):
            bail("BatchNorm batch statistics are consumed in-graph")
            continue
        dshape = node_shapes.get((id(bn.inputs[0][0]), bn.inputs[0][1]))
        if dshape is None or len(dshape) != 4:
            bail(f"data shape unknown or not NCHW 4-D ({dshape})")
            continue
        b, c, h, w = dshape
        nf = cattrs.get("num_filter")
        wshape = node_shapes.get((id(node.inputs[1][0]),
                                  node.inputs[1][1]))
        out_c = int(nf) if nf is not None else (
            int(wshape[0]) if wshape else None)
        if out_c is None:
            bail("num_filter unknown")
            continue
        per_dtype = [select_conv_tiles(out_c, h * w, c, dt)
                     for dt in dtypes]
        if None in per_dtype:
            bail(conv_tile_failure(out_c, h * w, c,
                                   dtypes[per_dtype.index(None)]))
            continue
        tiles = per_dtype[0]
        claimed.update({id(bn)} | ({id(relu)} if relu is not None
                                   else set()))
        sites[id(node)] = {"bn": bn, "relu": relu, "tiles": tiles}
        report["sites"].append({
            "conv": node.name, "bn": bn.name,
            "activation": relu.name if relu is not None else None,
            "batch": int(b), "spatial": int(h * w), "k": int(c),
            "n": out_c, "bo_tile": tiles[0], "bs_tile": tiles[1]})

    if not sites:
        return sym, report

    # -- rebuild: share untouched nodes, substitute fused ones ---------------
    memo: Dict[int, _Node] = {}
    outmap: Dict[tuple, tuple] = {}  # (id(old), idx) -> (new node, idx)

    def map_out(p, i):
        if (id(p), i) in outmap:
            return outmap[(id(p), i)]
        return build(p), i

    def build(node):
        if id(node) in memo:
            return memo[id(node)]
        if node.op is None:
            memo[id(node)] = node
            return node
        if id(node) in sites:
            m = sites[id(node)]
            bn, relu = m["bn"], m["relu"]
            battrs, cattrs = _attrs(bn), _attrs(node)
            inputs = [map_out(*bn.inputs[j]) for j in range(5)]
            inputs.append(map_out(*node.inputs[1]))
            no_bias = bool(cattrs.get("no_bias", False))
            if len(node.inputs) > 2 and not no_bias:
                inputs.append(map_out(*node.inputs[2]))
            else:
                no_bias = True
            attrs = {
                "eps": battrs.get("eps", 1e-3),
                "momentum": battrs.get("momentum", 0.9),
                "fix_gamma": battrs.get("fix_gamma", True),
                "use_global_stats": battrs.get("use_global_stats",
                                               False),
                "act_type": "relu" if relu is not None else None,
                "num_filter": cattrs.get("num_filter"),
                "no_bias": no_bias,
            }
            fused = _Node("_FusedBNReLUConv", node.name, attrs=attrs,
                          inputs=inputs, num_outputs=3,
                          user_attrs=node.user_attrs)
            fused.uid = node.uid
            memo[id(node)] = fused
            outmap[(id(node), 0)] = (fused, 0)
            return fused
        new_inputs = [map_out(p, i) for p, i in node.inputs]
        if all(np_ is p and ni == i for (np_, ni), (p, i)
               in zip(new_inputs, node.inputs)):
            memo[id(node)] = node
            return node
        nn = _Node(node.op, node.name, attrs=node.attrs,
                   inputs=new_inputs, num_outputs=node.num_outputs,
                   user_attrs=node.user_attrs)
        nn.uid = node.uid  # keep per-node RNG salts aligned
        memo[id(node)] = nn
        return nn

    new_outs = []
    for s in sym._output_symbols():
        n2, i2 = map_out(s._node, s._out_index)
        new_outs.append(Symbol(n2, i2))
    new_sym = new_outs[0] if len(new_outs) == 1 and sym._group is None \
        else Group(new_outs)
    return new_sym, report


def maybe_fuse(sym: Symbol, shapes: Dict[str, tuple], tag: str
               ) -> Tuple[Optional[Symbol], Optional[dict]]:
    """Executor entry point: run the pass when the flag allows, validate
    that the rewrite preserved argument/aux name order (the executors
    feed values positionally by the ORIGINAL symbol's lists), register
    the report for ``fusion_report()``. Returns ``(fused_sym | None,
    report | None)`` — None symbol means 'use the original'."""
    if not fusion_enabled():
        return None, None
    fused, report = fuse_symbol(sym, shapes)
    report = {"tag": tag, **report}
    _record(report)
    if not report["sites"]:
        return None, report
    if (fused.list_arguments() != sym.list_arguments()
            or fused.list_auxiliary_states()
            != sym.list_auxiliary_states()):
        # should not happen (the fused node preserves DFS input order);
        # refuse rather than feed values to the wrong names
        report["sites"] = []
        report["bailouts"].append(
            {"conv": None, "bn": None,
             "reason": "rewrite permuted argument order; discarded"})
        return None, report
    return fused, report
