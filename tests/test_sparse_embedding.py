"""Sparse embedding subsystem tests (round 13).

Pins the whole row-sparse path end to end (mxnet_tpu/sparse/ + the lazy
optimizer rules + the fused step's perturbation routing):

- dedup primitives: sorted-unique ids, duplicate summing, sentinel tail
  that never aliases row 0;
- the ``SparseEmbedding`` op: forward identical to dense ``Embedding``,
  op-level VJP identical to the dense gradient;
- fused-step equivalence: sparse-vs-dense training is BIT-IDENTICAL
  when every row is touched every step (sgd+momentum and adam — the
  documented lazy_update contract), and the lazy divergence under
  partial coverage is exactly the frozen-momentum rule, pinned at the
  functional-rule level;
- the acceptance regression: at 100k vocab the sparse train step moves
  strictly fewer XLA cost-analysis bytes than the dense-gradient step
  (the reason the subsystem exists);
- mesh sharding: 8-device in-process (tests/conftest.py forces 8 host
  devices) — lookup exact, updates confined to the owning shard,
  optimizer state shard-proportional, state round-trips bit-for-bit;
- serving: Predictor handles integer id inputs through the bucketed
  program path;
- telemetry (``sparse::`` metrics + ``sparse_report``), compile-key
  material, and the two-tower example end to end in mini mode;
- chaos: SIGKILL at the ``sparse_update`` faultinject site mid-epoch,
  then checkpoint auto-resume restores tables + lazy optimizer state
  bit-for-bit (sha256 digests across processes).
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
import mxnet_tpu.ndarray as nd
from mxnet_tpu.io import DataBatch
from mxnet_tpu.parallel import functional_opt, make_mesh
from mxnet_tpu.sparse import (RowSparseRows, ShardedEmbeddingTable,
                              dedup_rows, densify, scatter_rows,
                              sparse_embedding)

_TESTS = os.path.dirname(os.path.abspath(__file__))


# ---------------------------------------------------------------------------
# rowsparse primitives
# ---------------------------------------------------------------------------
class TestDedupRows:
    def test_duplicates_summed_sorted_with_sentinel_tail(self):
        ids = jnp.array([3, 1, 3, 0], jnp.int32)
        vals = jnp.arange(8, dtype=jnp.float32).reshape(4, 2)
        rs = dedup_rows(ids, vals, num_rows=6)
        assert isinstance(rs, RowSparseRows)
        np.testing.assert_array_equal(np.asarray(rs.ids), [0, 1, 3, 6])
        np.testing.assert_array_equal(
            np.asarray(rs.rows),
            [[6, 7], [2, 3], [0 + 4, 1 + 5], [0, 0]])

    def test_sentinel_never_aliases_row_zero(self):
        # all-duplicate batch: 3 of 4 slots are sentinel, zero rows
        ids = jnp.array([2, 2, 2, 2], jnp.int32)
        vals = jnp.ones((4, 3), jnp.float32)
        rs = dedup_rows(ids, vals, num_rows=5)
        np.testing.assert_array_equal(np.asarray(rs.ids), [2, 5, 5, 5])
        dense = np.asarray(densify(rs))
        assert dense.shape == (5, 3)
        np.testing.assert_array_equal(dense[2], [4, 4, 4])
        assert not dense[[0, 1, 3, 4]].any(), \
            "sentinel slots must contribute nothing (no row-0 aliasing)"

    def test_densify_matches_numpy_scatter_oracle(self):
        rng = np.random.RandomState(0)
        ids = rng.randint(0, 10, size=(6, 3)).astype(np.int32)
        vals = rng.randn(6, 3, 4).astype(np.float32)
        rs = dedup_rows(jnp.asarray(ids), jnp.asarray(vals), num_rows=10)
        oracle = np.zeros((10, 4), np.float32)
        for i, v in zip(ids.reshape(-1), vals.reshape(-1, 4)):
            oracle[i] += v
        np.testing.assert_allclose(np.asarray(densify(rs)), oracle,
                                   rtol=1e-6, atol=1e-6)

    def test_scatter_rows_drops_sentinel(self):
        rs = dedup_rows(jnp.array([1, 1], jnp.int32),
                        jnp.ones((2, 2), jnp.float32), num_rows=3)
        out = np.asarray(scatter_rows(jnp.zeros((3, 2), jnp.float32),
                                      rs, scale=0.5))
        np.testing.assert_array_equal(out, [[0, 0], [1, 1], [0, 0]])

    def test_capacity_override_still_covers_all_rows(self):
        ids = jnp.array([4, 0], jnp.int32)
        vals = jnp.ones((2, 1), jnp.float32)
        rs = dedup_rows(ids, vals, num_rows=5, capacity=4)
        assert rs.ids.shape == (4,)
        np.testing.assert_array_equal(np.asarray(rs.ids), [0, 4, 5, 5])

    def test_pytree_roundtrip(self):
        rs = dedup_rows(jnp.array([1], jnp.int32),
                        jnp.ones((1, 2), jnp.float32), num_rows=4)
        leaves, treedef = jax.tree_util.tree_flatten(rs)
        rs2 = jax.tree_util.tree_unflatten(treedef, leaves)
        assert isinstance(rs2, RowSparseRows) and rs2.num_rows == 4

    def test_undersized_capacity_raises_eagerly(self):
        """capacity below the true unique count would silently drop the
        largest ids' rows inside a trace; on concrete ids it must raise
        instead (the documented capacity >= unique-count contract)."""
        ids = jnp.array([0, 3, 7, 9], jnp.int32)
        vals = jnp.ones((4, 2), jnp.float32)
        with pytest.raises(ValueError, match="capacity=2 is below"):
            dedup_rows(ids, vals, num_rows=10, capacity=2)
        # a cap that does cover the uniques is fine
        rs = dedup_rows(jnp.array([5, 5, 5, 1], jnp.int32), vals,
                        num_rows=10, capacity=2)
        np.testing.assert_array_equal(np.asarray(rs.ids), [1, 5])


# ---------------------------------------------------------------------------
# op level: forward + VJP vs dense Embedding
# ---------------------------------------------------------------------------
class TestSparseEmbeddingOp:
    def test_forward_matches_dense_take(self):
        rng = np.random.RandomState(0)
        w = jnp.asarray(rng.randn(7, 3).astype(np.float32))
        ids = jnp.asarray(rng.randint(0, 7, size=(4, 2)).astype(np.int32))
        np.testing.assert_array_equal(
            np.asarray(sparse_embedding(ids, w)),
            np.asarray(jnp.take(w, ids, axis=0)))

    def test_vjp_matches_dense_embedding_gradient(self):
        rng = np.random.RandomState(1)
        w = jnp.asarray(rng.randn(9, 4).astype(np.float32))
        ids = jnp.asarray(
            rng.randint(0, 9, size=(5, 3)).astype(np.int32))
        cot = jnp.asarray(rng.randn(5, 3, 4).astype(np.float32))

        def loss_sparse(w):
            return jnp.vdot(sparse_embedding(ids, w), cot)

        def loss_dense(w):
            return jnp.vdot(jnp.take(w, ids, axis=0), cot)

        gs = np.asarray(jax.grad(loss_sparse)(w))
        gd = np.asarray(jax.grad(loss_dense)(w))
        np.testing.assert_allclose(gs, gd, rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# fused step routing + equivalence
# ---------------------------------------------------------------------------
def _two_layer(op, vocab, dim, hidden=4):
    data = mx.sym.Variable("data")
    emb = getattr(mx.sym, op)(data=data, input_dim=vocab, output_dim=dim,
                              name="emb")
    fc = mx.sym.FullyConnected(mx.sym.Flatten(emb), num_hidden=hidden,
                               name="fc")
    return mx.sym.SoftmaxOutput(fc, name="softmax")


def _train_emb(op, ids_steps, label, optimizer, opt_params, vocab, dim,
               seed=2):
    rng = np.random.RandomState(seed)
    mod = mx.mod.Module(_two_layer(op, vocab, dim),
                        data_names=("data",),
                        label_names=("softmax_label",), context=mx.cpu())
    mod.bind(data_shapes=[("data", ids_steps[0].shape)],
             label_shapes=[("softmax_label", label.shape)])
    mod.init_params()
    w0 = (rng.randn(vocab, dim) * 0.1).astype(np.float32)
    fcw = (rng.randn(4, ids_steps[0].shape[1] * dim) * 0.1) \
        .astype(np.float32)
    mod.set_params({"emb_weight": mx.nd.array(w0),
                    "fc_weight": mx.nd.array(fcw),
                    "fc_bias": mx.nd.array(np.zeros(4, np.float32))}, {},
                   allow_missing=True)
    mod.init_optimizer(optimizer=optimizer, optimizer_params=opt_params)
    for ids in ids_steps:
        b = DataBatch(data=[nd.array(ids)], label=[nd.array(label)])
        mod.forward(b, is_train=True)
        mod.backward()
        mod.update()
    args, _ = mod.get_params()
    return mod, {n: np.asarray(v._data) for n, v in args.items()}


class TestFusedEquivalence:
    VOCAB, DIM = 12, 6

    def _full_coverage_ids(self, steps=4):
        # every row 0..vocab-1 appears every step: lazy touch set ==
        # full table, so lazy_update must be bit-identical to dense
        return [np.arange(self.VOCAB).reshape(6, 2).astype(np.int32)
                for _ in range(steps)]

    @pytest.mark.parametrize("optimizer,params", [
        ("sgd", {"learning_rate": 0.5, "momentum": 0.9, "wd": 0.01}),
        ("adam", {"learning_rate": 0.01, "wd": 0.01}),
    ])
    def test_full_coverage_bit_identical_to_dense(self, optimizer, params):
        label = np.random.RandomState(1).randint(0, 4, size=(6,)) \
            .astype(np.float32)
        ids_steps = self._full_coverage_ids()
        sp_mod, sp = _train_emb("SparseEmbedding", ids_steps, label,
                                optimizer, params, self.VOCAB, self.DIM)
        dn_mod, dn = _train_emb("Embedding", ids_steps, label,
                                optimizer, params, self.VOCAB, self.DIM)
        assert len(sp_mod._fused._sparse_sites) == 1
        assert len(dn_mod._fused._sparse_sites) == 0
        for n in sp:
            np.testing.assert_array_equal(sp[n], dn[n], err_msg=n)

    def test_partial_coverage_runs_and_stays_finite(self):
        """Varying partial coverage is where lazy semantics DIVERGE
        from dense (untouched rows keep frozen momentum — the
        documented decay-on-touch rule); the routed path must still
        train stably."""
        rng = np.random.RandomState(3)
        label = rng.randint(0, 4, size=(6,)).astype(np.float32)
        ids_steps = [rng.randint(0, self.VOCAB, size=(6, 2))
                     .astype(np.int32) for _ in range(4)]
        mod, params = _train_emb(
            "SparseEmbedding", ids_steps, label, "sgd",
            {"learning_rate": 0.5, "momentum": 0.9}, self.VOCAB, self.DIM)
        assert all(np.isfinite(v).all() for v in params.values())

    def test_lazy_rule_freezes_untouched_momentum(self):
        """The decay-on-touch contract at the functional-rule level:
        after a full-coverage step builds momentum, a second step
        touching only row 0 moves row 0 alone — the dense rule would
        carry every row forward on its momentum."""
        fopt = functional_opt.create("sgd", momentum=0.9)
        p = jnp.ones((3, 2), jnp.float32)
        s = fopt.init(p)
        full = dedup_rows(jnp.array([0, 1, 2], jnp.int32),
                          jnp.ones((3, 2), jnp.float32), num_rows=3)
        p, s = fopt.row_update(p, full.ids, full.rows, s,
                               jnp.float32(0.1), jnp.uint32(1),
                               jnp.float32(0.0))
        only0 = dedup_rows(jnp.array([0], jnp.int32),
                           jnp.ones((1, 2), jnp.float32), num_rows=3)
        p_lazy, s_lazy = fopt.row_update(p, only0.ids, only0.rows, s,
                                         jnp.float32(0.1), jnp.uint32(2),
                                         jnp.float32(0.0))
        p_dense, _ = fopt.update(p, jnp.zeros((3, 2)).at[0].set(1.0), s,
                                 jnp.float32(0.1), jnp.uint32(2),
                                 jnp.float32(0.0), None)
        # row 0 (touched): identical under both rules
        np.testing.assert_allclose(np.asarray(p_lazy)[0],
                                   np.asarray(p_dense)[0], atol=1e-7)
        # rows 1-2 (untouched): lazy freezes them, dense coasts on
        # momentum
        np.testing.assert_array_equal(np.asarray(p_lazy)[1:],
                                      np.asarray(p)[1:])
        assert np.abs(np.asarray(p_dense)[1:] -
                      np.asarray(p)[1:]).max() > 1e-3
        # untouched momentum is bit-frozen too
        np.testing.assert_array_equal(
            np.asarray(jax.tree_util.tree_leaves(s_lazy)[0])[1:],
            np.asarray(jax.tree_util.tree_leaves(s)[0])[1:])

    def test_telemetry_counters_populate(self):
        label = np.zeros((6,), np.float32)
        with mx.config.override("MXTPU_SPARSE_STATS", "1"):
            mx.sparse.sparse_report(reset=True)
            _train_emb("SparseEmbedding", self._full_coverage_ids(3),
                       label, "sgd", {"learning_rate": 0.1},
                       self.VOCAB, self.DIM)
            rep = mx.sparse.sparse_report()
        assert rep["steps"] == 3
        assert rep["ids_total"] == 3 * 12
        assert rep["touched_rows"] == 3 * 12
        assert rep["dedup_ratio"] == 1.0
        assert rep["gather_bytes"] == 12 * self.DIM * 4
        assert rep["scatter_bytes"] == 12 * self.DIM * 4
        assert rep["sites"] == 1

    def test_compile_key_carries_sparse_material(self):
        label = np.zeros((6,), np.float32)
        mod, _ = _train_emb("SparseEmbedding", self._full_coverage_ids(1),
                            label, "sgd", {"learning_rate": 0.1},
                            self.VOCAB, self.DIM)
        fused = mod._fused
        key = fused._program_key(("sig",))
        mat = key.materials["extra"]["sparse"]
        assert len(mat) == 1
        assert mat[0][1] == "emb_weight" and mat[0][3] == self.VOCAB
        # a dense-vs-sparse flip of the same graph must change the key
        sites = fused._sparse_sites
        try:
            fused._sparse_sites = []
            key_dense = fused._program_key(("sig",))
        finally:
            fused._sparse_sites = sites
        assert key.digest != key_dense.digest
        assert "extra" in key.diff(key_dense)


# ---------------------------------------------------------------------------
# tied table weights: multi-consumer safety
# ---------------------------------------------------------------------------
def _tied_net(op, vocab, dim):
    """Input/output-tied embeddings: ONE table variable feeds the
    lookup AND the softmax projection (the classic tied decoder) — a
    weight with a non-site consumer must never route row-sparse."""
    data = mx.sym.Variable("data")
    w = mx.sym.Variable("emb_weight")
    emb = getattr(mx.sym, op)(data=data, weight=w, input_dim=vocab,
                              output_dim=dim, name="emb")
    logits = mx.sym.FullyConnected(mx.sym.Flatten(emb), weight=w,
                                   num_hidden=vocab, no_bias=True,
                                   name="dec")
    return mx.sym.SoftmaxOutput(logits, name="softmax")


class TestTiedWeightFallback:
    VOCAB, DIM = 10, 5

    def test_find_sites_excludes_multi_consumer_weight(self):
        from mxnet_tpu.sparse import find_sites
        net = _tied_net("_contrib_SparseEmbedding", self.VOCAB, self.DIM)
        fb = []
        sites = find_sites(net, ["emb_weight"],
                           ["data", "softmax_label"], fallbacks=fb)
        assert sites == [], \
            "a table also feeding a dense op must stay on the dense path"
        assert fb == [{"weight": "emb_weight", "node": "emb",
                       "reason": "shared_weight"}]

    def test_two_qualifying_sites_sharing_table_still_route(self):
        """Several sites over ONE table are fine — the fused step merges
        their rows before one dedup; only a NON-site consumer trips the
        fallback."""
        from mxnet_tpu.sparse import find_sites
        a, b = mx.sym.Variable("ids_a"), mx.sym.Variable("ids_b")
        w = mx.sym.Variable("emb_weight")
        e1 = mx.sym._contrib_SparseEmbedding(
            data=a, weight=w, input_dim=self.VOCAB, output_dim=self.DIM,
            name="ea")
        e2 = mx.sym._contrib_SparseEmbedding(
            data=b, weight=w, input_dim=self.VOCAB, output_dim=self.DIM,
            name="eb")
        fc = mx.sym.FullyConnected(mx.sym.Flatten(e1 + e2),
                                   num_hidden=4, name="fc")
        net = mx.sym.SoftmaxOutput(fc, name="softmax")
        fb = []
        sites = find_sites(net, ["emb_weight", "fc_weight", "fc_bias"],
                           ["ids_a", "ids_b", "softmax_label"],
                           fallbacks=fb)
        assert len(sites) == 2 and not fb

    def _train(self, op, ids_steps, labels):
        mod = mx.mod.Module(_tied_net(op, self.VOCAB, self.DIM),
                            data_names=("data",),
                            label_names=("softmax_label",),
                            context=mx.cpu())
        mod.bind(data_shapes=[("data", ids_steps[0].shape)],
                 label_shapes=[("softmax_label", labels.shape)])
        mod.init_params()
        w0 = (np.random.RandomState(7).randn(self.VOCAB, self.DIM)
              * 0.1).astype(np.float32)
        mod.set_params({"emb_weight": mx.nd.array(w0)}, {},
                       allow_missing=True)
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": 0.5,
                                             "momentum": 0.9})
        for ids in ids_steps:
            batch = DataBatch(data=[nd.array(ids)],
                              label=[nd.array(labels)])
            mod.forward(batch, is_train=True)
            mod.backward()
            mod.update()
        args, _ = mod.get_params()
        return mod, np.asarray(args["emb_weight"]._data)

    def test_tied_weight_trains_identical_to_dense(self):
        """The review regression: before the consumer check, the fused
        step routed the tied table row-sparse and silently dropped the
        projection path's gradient. The tied sparse net must train
        exactly like the tied dense-Embedding net (both on the dense
        custom-VJP path), with the fallback counted."""
        from mxnet_tpu.telemetry import registry as treg
        rng = np.random.RandomState(0)
        ids_steps = [rng.randint(0, self.VOCAB, (6, 1)).astype(np.int32)
                     for _ in range(3)]
        labels = rng.randint(0, self.VOCAB, (6,)).astype(np.float32)
        before = treg.counter("sparse::dense_fallback").get()
        sp_mod, sp = self._train("_contrib_SparseEmbedding", ids_steps,
                                 labels)
        dn_mod, dn = self._train("Embedding", ids_steps, labels)
        assert len(sp_mod._fused._sparse_sites) == 0, \
            "tied table must not be routed row-sparse"
        assert treg.counter("sparse::dense_fallback").get() >= before + 1
        np.testing.assert_array_equal(sp, dn, err_msg=(
            "tied-weight sparse training diverged from the dense path — "
            "a consumer's gradient was dropped"))
        # and the table really moved (the test isn't vacuous)
        w0 = (np.random.RandomState(7).randn(self.VOCAB, self.DIM)
              * 0.1).astype(np.float32)
        assert np.abs(sp - w0).max() > 1e-4


# ---------------------------------------------------------------------------
# the acceptance regression: grad bytes at 100k vocab
# ---------------------------------------------------------------------------
def _pooled_classifier(op, vocab, dim):
    data = mx.sym.Variable("data")
    emb = getattr(mx.sym, op)(data=data, input_dim=vocab,
                              output_dim=dim, name="emb")
    pooled = mx.sym.sum(emb, axis=1)
    fc = mx.sym.FullyConnected(pooled, num_hidden=2, name="fc")
    return mx.sym.SoftmaxOutput(fc, name="softmax")


def test_sparse_step_bytes_strictly_below_dense_100k_vocab():
    """The reason the subsystem exists, pinned by what the compiled
    step holds: on a 100k-row table the row-sparse train step (gather +
    rows-only dedup + lazy scatter) keeps no table-sized temporary,
    where the dense step's gradient and momentum update are table-sized;
    and it moves fewer bytes by XLA's count. A site that fell back to
    the dense path fails all three."""
    from mxnet_tpu.telemetry import registry as treg
    vocab, dim, batch, slen = 100_000, 16, 32, 8
    table_bytes = vocab * dim * 4
    fallbacks = treg.counter("sparse::dense_fallback").get()

    def step_bytes(op):
        mod = mx.mod.Module(_pooled_classifier(op, vocab, dim),
                            data_names=("data",),
                            label_names=("softmax_label",),
                            context=mx.cpu())
        mod.bind(data_shapes=[("data", (batch, slen))],
                 label_shapes=[("softmax_label", (batch,))])
        mod.init_params()
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": 0.1,
                                             "momentum": 0.9})
        fused = mod._fused
        rng = np.random.RandomState(0)
        feed = {"data": mx.nd.array(
                    rng.randint(0, vocab, (batch, slen))
                    .astype(np.int32)).data,
                "softmax_label": mx.nd.array(
                    rng.randint(0, 2, (batch,))
                    .astype(np.float32)).data}
        cost = fused.step_cost(feed)
        return (float(cost.get("bytes accessed", 0.0)),
                int(fused.step_memory(feed)["temp_bytes"]),
                len(fused._sparse_sites))

    sparse_b, sparse_tmp, sparse_sites = step_bytes("SparseEmbedding")
    dense_b, dense_tmp, dense_sites = step_bytes("Embedding")
    assert sparse_sites == 1 and dense_sites == 0
    assert treg.counter("sparse::dense_fallback").get() == fallbacks
    assert sparse_b > 0 and dense_b > 0
    assert sparse_b < dense_b, (
        f"sparse step bytes {sparse_b:.3e} not strictly below dense "
        f"{dense_b:.3e}")
    # the gap is structural, not a ratio of XLA's counts (which charge
    # an in-place row update the whole table): the dense step holds a
    # table-sized gradient among its temporaries, the sparse step none
    assert sparse_tmp < table_bytes <= dense_tmp, (
        f"temporaries: sparse {sparse_tmp}, dense {dense_tmp}, one "
        f"table {table_bytes}")


# ---------------------------------------------------------------------------
# mesh sharding (8 in-process devices from conftest's XLA flag)
# ---------------------------------------------------------------------------
class TestShardedEmbeddingTable:
    VOCAB, DIM = 64, 8

    def _mesh(self):
        assert jax.device_count() >= 8, \
            "conftest must force 8 host devices"
        return make_mesh({"data": 8})

    def _table(self, rng, **kw):
        W0 = rng.randn(self.VOCAB, self.DIM).astype(np.float32)
        kw.setdefault("optimizer", "sgd")
        return W0, ShardedEmbeddingTable(W0, self._mesh(), **kw)

    def test_lookup_exact_and_batch_sharded(self):
        rng = np.random.RandomState(0)
        W0, tab = self._table(rng)
        ids = rng.randint(0, self.VOCAB, size=(16, 3)).astype(np.int32)
        out = tab.lookup(ids)
        assert out.shape == (16, 3, self.DIM)
        np.testing.assert_array_equal(np.asarray(out), W0[ids])

    @pytest.mark.parametrize("optimizer,kw", [
        ("sgd", {"momentum": 0.9}),
        ("adam", {}),
    ])
    def test_update_matches_single_device_oracle(self, optimizer, kw):
        rng = np.random.RandomState(1)
        W0, tab = self._table(rng, optimizer=optimizer, **kw)
        fopt = functional_opt.create(optimizer, **kw)
        p = jnp.asarray(W0)
        s = fopt.init(p)
        for step in range(3):
            gids = rng.randint(0, self.VOCAB, size=(24,)) \
                .astype(np.int32)
            grows = rng.randn(24, self.DIM).astype(np.float32)
            tab.apply_grad(gids, grows, lr=0.1, wd=0.01)
            rs = dedup_rows(jnp.asarray(gids), jnp.asarray(grows),
                            num_rows=self.VOCAB)
            p, s = fopt.row_update(p, rs.ids, rs.rows, s,
                                   jnp.float32(0.1),
                                   jnp.uint32(step + 1),
                                   jnp.float32(0.01))
        np.testing.assert_allclose(tab.dense(), np.asarray(p),
                                   rtol=1e-6, atol=1e-6)
        for a, b in zip(tab.state_arrays(),
                        jax.tree_util.tree_leaves(s)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6)

    def test_update_confined_to_owning_shard(self):
        """The acceptance dryrun: ids inside shard 0's window leave
        every other shard's rows (and optimizer state) bit-untouched —
        rebased out-of-window writes are structurally dropped, never
        wrapped into a neighbor shard's tail."""
        rng = np.random.RandomState(2)
        _, tab = self._table(rng, momentum=0.9)
        before = tab.dense().copy()
        state_before = [a.copy() for a in tab.state_arrays()]
        shard = tab.shard_rows
        tab.apply_grad(np.array([1, 2, shard - 1], np.int32),
                       np.ones((3, self.DIM), np.float32), lr=0.1)
        after = tab.dense()
        np.testing.assert_array_equal(before[shard:], after[shard:])
        assert np.abs(after[:shard] - before[:shard]).max() > 0
        for sb, sa in zip(state_before, tab.state_arrays()):
            np.testing.assert_array_equal(sb[shard:],
                                          np.asarray(sa)[shard:])

    def test_optimizer_state_is_shard_proportional(self):
        rng = np.random.RandomState(3)
        _, tab = self._table(rng, optimizer="adam")
        assert tab.shard_rows == self.VOCAB // 8
        assert tab.per_device_state_rows() == tab.shard_rows, \
            "per-device optimizer state must hold one row shard, " \
            "never the full table"

    def test_state_roundtrip_bit_for_bit(self):
        rng = np.random.RandomState(4)
        W0, tab = self._table(rng, momentum=0.9)
        tab.apply_grad(rng.randint(0, self.VOCAB, size=(16,))
                       .astype(np.int32),
                       rng.randn(16, self.DIM).astype(np.float32),
                       lr=0.1)
        tab2 = ShardedEmbeddingTable(np.zeros_like(W0), self._mesh(),
                                     optimizer="sgd", momentum=0.9)
        tab2.load(tab.dense(), tab.state_arrays(), t=tab._t)
        np.testing.assert_array_equal(tab2.dense(), tab.dense())
        for a, b in zip(tab2.state_arrays(), tab.state_arrays()):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_vocab_must_divide_mesh(self):
        with pytest.raises(ValueError, match="multiple"):
            ShardedEmbeddingTable(np.zeros((63, 4), np.float32),
                                  self._mesh())

    def test_requires_row_capable_optimizer(self):
        with pytest.raises(ValueError, match="row-update"):
            ShardedEmbeddingTable(np.zeros((64, 4), np.float32),
                                  self._mesh(), optimizer="sgd",
                                  lazy_update=False)


# ---------------------------------------------------------------------------
# serving: integer ids through the Predictor
# ---------------------------------------------------------------------------
def test_predictor_serves_integer_ids():
    vocab, dim = 20, 4
    sym = _two_layer("SparseEmbedding", vocab, dim)
    mod = mx.mod.Module(sym, data_names=("data",),
                        label_names=("softmax_label",), context=mx.cpu())
    mod.bind(data_shapes=[("data", (6, 2))],
             label_shapes=[("softmax_label", (6,))])
    mod.init_params(mx.init.Xavier())
    arg_params, aux_params = mod.get_params()
    pred = mx.serving.Predictor(sym, arg_params, aux_params,
                                data_names=("data",),
                                data_shapes={"data": (2,)},
                                buckets=(4, 8))
    rng = np.random.RandomState(0)
    ids = rng.randint(0, vocab, size=(6, 2)).astype(np.int32)
    out = pred.predict({"data": ids})
    assert out.shape == (6, 4)
    # oracle: the module's own forward
    mod.forward(DataBatch(data=[nd.array(ids)],
                          label=[nd.array(np.zeros(6, np.float32))]),
                is_train=False)
    ref = np.asarray(mod.get_outputs()[0]._data)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# the two-tower example, mini mode, end to end
# ---------------------------------------------------------------------------
def test_two_tower_example_end_to_end(tmp_path):
    example_dir = os.path.abspath(
        os.path.join(_TESTS, os.pardir, "examples", "sparse"))
    sys.path.insert(0, example_dir)
    try:
        import two_tower
        res = two_tower.main(["--mini", "--workdir", str(tmp_path)])
    finally:
        sys.path.remove(example_dir)
    assert res["acc"] > 0.5
    assert res["scores"].shape[0] == 16
    assert res["sparse"]["sites"] == 2
    assert res["sparse"]["steps"] > 0
    # fit() checkpointed through the manager
    assert os.path.exists(os.path.join(str(tmp_path), "ckpt",
                                       "ckpt-000001", "MANIFEST.json"))


# ---------------------------------------------------------------------------
# chaos: SIGKILL mid row-scatter, resume bit-for-bit
# ---------------------------------------------------------------------------
WORKER = os.path.join(_TESTS, "sparse_worker.py")


@pytest.mark.chaos
def test_sigkill_mid_sparse_update_resumes_bit_for_bit(tmp_path):
    """The r13 acceptance drill: the fused step is SIGKILLed at the
    ``sparse_update`` faultinject site mid-epoch-2 (after the epoch-1
    checkpoint committed). The resumed process must restore the
    embedding tables AND the lazy optimizer state bit-for-bit (sha256
    digest equality across processes), then finish training cleanly."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS",
                        "MXTPU_FAULT_INJECT")}
    env["JAX_PLATFORMS"] = "cpu"

    def run(args, fault=None):
        e = dict(env)
        if fault is not None:
            e["MXTPU_FAULT_INJECT"] = fault
        return subprocess.run([sys.executable, WORKER] + args,
                              capture_output=True, text=True, env=e,
                              timeout=600)

    wd = str(tmp_path)
    # run 1: 8 steps/epoch; step 12 is mid-epoch-2
    r1 = run([wd, "4"], fault="sparse_update:step=12:action=kill")
    assert r1.returncode != 0, "killed run must not exit cleanly"
    assert "faultinject: SIGKILL at site 'sparse_update'" in r1.stdout
    assert not os.path.exists(os.path.join(wd, "done"))
    digest1 = os.path.join(wd, "digest-1")
    assert os.path.exists(digest1), \
        "epoch-1 digest must precede the kill"
    assert not os.path.exists(os.path.join(wd, "digest-2"))

    # run 2: restore + digest the restored state, then finish
    r2 = run([wd, "4", "--digest-restored"])
    assert r2.returncode == 0, r2.stdout + r2.stderr
    assert "continuing at epoch 1" in r2.stdout, r2.stdout[-3000:]
    m = [ln for ln in r2.stdout.splitlines()
         if ln.startswith("restored epoch=1 digest=")]
    assert m, r2.stdout[-3000:]
    restored = m[0].split("digest=")[1].strip()
    with open(digest1) as f:
        saved = f.read().strip()
    assert restored == saved, (
        "checkpoint restore must reproduce tables + lazy optimizer "
        "state bit-for-bit")
    assert os.path.exists(os.path.join(wd, "done"))
