"""Mesh-sharded embedding tables: shard_map gather / rows-only update.

Design reference: PAPERS.md "Automatic Cross-Replica Sharding of Weight
Update in Data-Parallel Training" — the table's ROWS are partitioned
over the mesh's data axis, and, critically, so is the optimizer state:
each device initializes and updates only its ``vocab / ndev`` row shard,
so per-device optimizer memory and update FLOPs scale DOWN with the mesh
instead of replicating the full table everywhere (the KVStore
``PullRowSparse`` economics of PAPER.md L6, rebuilt on GSPMD).

The two collectives are explicit ``shard_map`` bodies, not GSPMD
inference, so the sharding is a contract rather than a hope:

- gather: ``all_gather`` the row shards (the weights materialize
  transiently for the lookup — activations are the small term), then a
  local take over the device's batch shard;
- update: the deduplicated rows are computed once (replicated), then
  every device rebases the unique ids into its own shard window and
  applies the lazy optimizer rule with out-of-shard writes dropped —
  no scatter ever crosses a shard boundary.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from jax import shard_map

from .rowsparse import RowSparseRows, dedup_rows

__all__ = ["ShardedEmbeddingTable", "shard_spec"]


def shard_spec(mesh, axis="data"):
    """NamedSharding partitioning rows over ``axis`` (dim replicated)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    return NamedSharding(mesh, P(axis, None))


class ShardedEmbeddingTable:
    """One ``(vocab, dim)`` table row-sharded over a mesh axis, with
    lazy (rows-touched-only) optimizer state sharded the same way.

    ``optimizer`` names a functional rule with row support (``sgd``,
    ``adam`` — parallel/functional_opt.py); hyperparameters pass
    through. ``vocab`` must divide evenly by the axis size (the caller
    pads its vocabulary; a remainder shard would make every id-rebase
    shape device-dependent).
    """

    def __init__(self, table, mesh, axis="data", optimizer="sgd",
                 **opt_kwargs):
        from ..parallel import functional_opt
        from ..telemetry import registry as _treg
        table = jnp.asarray(table)
        if table.ndim != 2:
            raise ValueError("embedding table must be (vocab, dim)")
        self.mesh = mesh
        self.axis = axis
        self.ndev = mesh.shape[axis]
        vocab = int(table.shape[0])
        if vocab % self.ndev:
            raise ValueError(
                f"vocab {vocab} must be a multiple of the '{axis}' axis "
                f"size {self.ndev} — pad the vocabulary")
        self.vocab = vocab
        self.dim = int(table.shape[1])
        self.shard_rows = vocab // self.ndev
        self._fopt = functional_opt.create(optimizer, **opt_kwargs)
        if self._fopt.row_update is None:
            raise ValueError(
                f"optimizer '{optimizer}' has no lazy row-update rule; "
                f"row-capable: {functional_opt.row_supported()}")
        self.sharding = shard_spec(mesh, axis)
        self.table = jax.device_put(table, self.sharding)
        # optimizer state: table-shaped leaves land row-sharded too —
        # per-device state is shard_rows/vocab of the dense equivalent
        self.state = tuple(jax.device_put(s, self.sharding)
                           for s in self._fopt.init(table))
        self._t = 0
        self._lookup_jit = None
        self._update_jit = None
        _treg.counter("sparse::sharded_tables").inc()

    # -- forward ---------------------------------------------------------------
    def _build_lookup(self):
        from jax.sharding import PartitionSpec as P
        axis = self.axis

        def gather(lw, lids):
            w_full = jax.lax.all_gather(lw, axis, axis=0, tiled=True)
            return jnp.take(w_full, lids.astype(jnp.int32), axis=0)

        fn = shard_map(gather, mesh=self.mesh,
                       in_specs=(P(axis, None), P(axis)),
                       out_specs=P(axis))
        self._lookup_jit = jax.jit(fn)

    def lookup(self, ids):
        """Batch-sharded lookup: ``ids`` ``(batch, ...)`` with batch
        divisible by the axis size; returns ``ids.shape + (dim,)``
        sharded over the batch axis."""
        if self._lookup_jit is None:
            self._build_lookup()
        ids = jnp.asarray(ids)
        lead = ids.reshape(ids.shape[0], -1)
        out = self._lookup_jit(self.table, lead)
        return out.reshape(ids.shape + (self.dim,))

    # -- update ----------------------------------------------------------------
    def _build_update(self):
        from jax.sharding import PartitionSpec as P
        axis = self.axis
        fopt = self._fopt
        shard_rows = self.shard_rows

        def update(lw, lstate, uids, rows, lr, t, wd):
            # uids/rows are replicated; each device rebases the global
            # ids into its shard window. Out-of-window ids map to the
            # NONNEGATIVE sentinel ``shard_rows``: a negative local id
            # would wrap around in ``.at[]`` (python indexing semantics
            # survive even under mode="drop") and corrupt the tail of
            # the shard — only a past-the-end id is structurally
            # dropped. Sentinel rows read clipped values (harmless,
            # discarded) and write nothing.
            lo = jax.lax.axis_index(axis) * shard_rows
            local = uids - lo
            local = jnp.where((local < 0) | (local >= shard_rows),
                              shard_rows, local)
            return fopt.row_update(lw, local, rows, lstate, lr, t, wd)

        fn = shard_map(
            update, mesh=self.mesh,
            in_specs=(P(axis, None), P(axis, None), P(), P(), P(), P(),
                      P()),
            out_specs=(P(axis, None), P(axis, None)))
        self._update_jit = jax.jit(fn, donate_argnums=(0, 1))

    def apply_rows(self, rs: RowSparseRows, lr, wd=0.0):
        """Apply one deduplicated row-gradient (rows aligned with
        ``rs.ids``, sentinel tail dropped) under the lazy rule."""
        if self._update_jit is None:
            self._build_update()
        self._t += 1
        self.table, self.state = self._update_jit(
            self.table, self.state, rs.ids, rs.rows,
            jnp.float32(lr), jnp.uint32(self._t), jnp.float32(wd))

    def apply_grad(self, ids, grad_rows, lr, wd=0.0):
        """Convenience: dedup per-occurrence ``(ids, grad_rows)`` then
        :meth:`apply_rows`."""
        self.apply_rows(dedup_rows(ids, grad_rows, num_rows=self.vocab),
                        lr, wd=wd)

    # -- views -----------------------------------------------------------------
    def dense(self):
        """The full table as one host array (checkpoint/test oracle)."""
        return np.asarray(self.table)

    def state_arrays(self):
        """Optimizer state leaves as host arrays (full logical shape;
        the device-resident layout stays sharded)."""
        return tuple(np.asarray(s) for s in self.state)

    def load(self, table, state=None, t=None):
        """Restore table (and optionally optimizer state / step count)
        from host arrays, re-sharding over the mesh."""
        self.table = jax.device_put(jnp.asarray(table), self.sharding)
        if state is not None:
            self.state = tuple(
                jax.device_put(jnp.asarray(s), self.sharding)
                for s in state)
        if t is not None:
            self._t = int(t)

    def per_device_state_rows(self):
        """Max rows of optimizer state held by any one device — the
        shard-proportionality pin (== shard_rows, never vocab)."""
        rows = 0
        for leaf in self.state:
            for s in leaf.addressable_shards:
                rows = max(rows, s.data.shape[0])
        return rows
