"""Subprocess helper for the sparse-update chaos drill
(test_sparse_embedding.py).

Trains a tiny two-tower SparseEmbedding model (sgd + momentum, so the
LAZY per-row optimizer state is nontrivial) with CheckpointManager
epoch snapshots, writing a sha256 digest of (arg params + aux + fused
optimizer state) at every epoch boundary — the exact bytes the manager
checkpoints at that boundary.

The parent arms ``MXTPU_FAULT_INJECT=sparse_update:step=N:action=kill``
so run 1 SIGKILLs at the fused step's row-scatter commit boundary
mid-epoch. Run 2 (``--digest-restored``) restores the surviving
checkpoint, re-digests the restored state, and prints it next to the
checkpoint's epoch tag: the parent asserts it equals run 1's digest for
that epoch — checkpoint/resume restores the embedding tables AND the
lazy optimizer state bit-for-bit — then finishes training cleanly.

Usage: sparse_worker.py <workdir> <num_epoch> [--digest-restored]
"""
import argparse
import hashlib
import os
import pickle
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(_HERE, os.pardir))

import jax  # noqa: E402

# CPU drill: pin the platform BEFORE mxnet_tpu import, in code — a
# worker must stay off a chip its parent may hold, whatever the
# inherited environment says
jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402


def build_sym(n_users=32, n_items=16, embed_dim=4):
    user = mx.sym.Variable("user")
    item = mx.sym.Variable("item")
    u = mx.sym.SparseEmbedding(data=user, input_dim=n_users,
                               output_dim=embed_dim, name="user_emb")
    i = mx.sym.SparseEmbedding(data=item, input_dim=n_items,
                               output_dim=embed_dim, name="item_emb")
    x = mx.sym.Concat(mx.sym.Flatten(u), mx.sym.Flatten(i), dim=1)
    o = mx.sym.FullyConnected(x, num_hidden=2, name="fc")
    return mx.sym.SoftmaxOutput(o, name="softmax")


def state_digest(mod):
    """sha256 over params + aux + serialized fused optimizer state —
    the bit-for-bit identity of everything a checkpoint restores."""
    h = hashlib.sha256()
    args, auxs = mod.get_params()
    for coll in (args, auxs):
        for n in sorted(coll):
            h.update(n.encode())
            h.update(np.ascontiguousarray(
                np.asarray(coll[n]._data)).tobytes())
    st = pickle.loads(mod._fused.get_states())
    h.update(str(st["num_update"]).encode())
    for n in sorted(st["state"]):
        h.update(n.encode())
        for leaf in jax.tree_util.tree_leaves(st["state"][n]):
            h.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
    return h.hexdigest()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("workdir")
    ap.add_argument("num_epoch", type=int)
    ap.add_argument("--digest-restored", action="store_true")
    args = ap.parse_args()

    import logging
    logging.basicConfig(level=logging.INFO, stream=sys.stdout, force=True)

    rng = np.random.RandomState(0)
    n = 128
    users = rng.randint(0, 32, size=(n, 1)).astype(np.int32)
    items = rng.randint(0, 16, size=(n, 1)).astype(np.int32)
    label = rng.randint(0, 2, size=(n,)).astype(np.float32)
    train = mx.io.NDArrayIter(
        data={"user": users, "item": items}, label={"softmax_label": label},
        batch_size=16, shuffle=False)

    mx.random.seed(0)
    mod = mx.mod.Module(symbol=build_sym(), data_names=("user", "item"),
                        label_names=("softmax_label",), context=mx.cpu())
    manager = mx.CheckpointManager(os.path.join(args.workdir, "ckpt"),
                                   async_save=False)

    if args.digest_restored:
        # bind/init, restore the surviving checkpoint, digest what came
        # back BEFORE any further training touches it
        mod.bind(data_shapes=train.provide_data,
                 label_shapes=train.provide_label)
        mod.init_params(mx.init.Xavier())
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": 0.1,
                                             "momentum": 0.9})
        state = manager.load_latest()
        assert state is not None, "no checkpoint survived the kill"
        manager.restore(mod, state)
        print(f"restored epoch={state.meta['epoch']} "
              f"digest={state_digest(mod)}", flush=True)

    def _digest_cb(epoch, sym, arg, aux):
        path = os.path.join(args.workdir, f"digest-{epoch + 1}")
        with open(path, "w") as f:
            f.write(state_digest(mod))

    mod.fit(train, num_epoch=args.num_epoch, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            initializer=mx.init.Xavier(), eval_metric="acc",
            epoch_end_callback=_digest_cb,
            checkpoint_manager=manager, auto_resume=True)

    with open(os.path.join(args.workdir, "done"), "w") as f:
        f.write(state_digest(mod))
    print("training complete", flush=True)


if __name__ == "__main__":
    main()
