"""Model-parallel LSTM language model.

TPU-native rebuild of the reference's model-parallel LSTM
(reference: example/model-parallel/lstm/lstm.py:65-100 — layers pinned to
different GPUs via group2ctx + _CrossDeviceCopy). On TPU the idiomatic
form is sharding, not placement: the mesh has a 'model' axis, the LSTM
gate weights shard over it (param_spec_fn), and XLA inserts the
collectives group2ctx's cross-device copies did by hand.

Run: python train.py --num-epoch 3      (8 virtual devices when no TPU)
"""
import argparse
import os

if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np


def make_data(num_seq=256, seq_len=32, vocab=32, seed=0):
    """Synthetic next-token task: token t+1 = (token t * 3 + 1) mod vocab,
    fully learnable by a small LSTM."""
    rng = np.random.RandomState(seed)
    starts = rng.randint(0, vocab, num_seq)
    seqs = np.zeros((num_seq, seq_len + 1), np.int64)
    seqs[:, 0] = starts
    for t in range(seq_len):
        seqs[:, t + 1] = (seqs[:, t] * 3 + 1) % vocab
    return seqs[:, :-1], seqs[:, 1:]


def build_net(vocab, hidden, num_layers):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import HybridBlock, nn, rnn

    class LM(HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.embed = nn.Embedding(vocab, hidden)
                self.lstm = rnn.LSTM(hidden, num_layers=num_layers,
                                     layout="NTC")
                self.out = nn.Dense(vocab, flatten=False)

        def forward(self, x):
            h = self.embed(x)
            h = self.lstm(h)
            return self.out(h)

    net = LM(prefix="mp_lstm_")
    net.initialize(mx.init.Xavier())
    return net


def train(num_epoch=3, batch_size=32, hidden=64, num_layers=2, vocab=32,
          lr=0.01, log=print):
    import jax
    from jax.sharding import PartitionSpec as P
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import TrainStep, make_mesh

    x, y = make_data(vocab=vocab)
    net = build_net(vocab, hidden, num_layers)

    n_dev = len(jax.devices())
    model_par = 4 if n_dev >= 8 else max(1, n_dev // 2)
    mesh = make_mesh({"data": n_dev // model_par, "model": model_par})

    def spec_fn(p):
        # LSTM gate weights are (4*hidden, in): shard the gate dim over
        # the model axis — the TP analog of the reference putting each
        # layer on its own GPU (example/model-parallel/lstm/lstm.py:65-100)
        if ("lstm" in p.name and p.name.endswith("weight")
                and len(p.shape) == 2 and p.shape[0] % model_par == 0):
            return P("model", None)
        return P()

    def seq_ce(logits, labels):
        import jax.numpy as jnp
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        picked = jnp.take_along_axis(
            logp, labels.astype(jnp.int32)[..., None], axis=-1)
        return -jnp.mean(picked)

    step = TrainStep(net, loss=seq_ce, optimizer="adam", lr=lr, mesh=mesh,
                     param_spec_fn=spec_fn)
    n = len(x)
    losses = []
    for epoch in range(num_epoch):
        order = np.random.RandomState(epoch).permutation(n)
        total, nb = 0.0, 0
        for lo in range(0, n - batch_size + 1, batch_size):
            idx = order[lo:lo + batch_size]
            loss = step(x[idx], y[idx])
            total += float(loss.asscalar())
            nb += 1
        losses.append(total / nb)
        log(f"epoch {epoch}: loss={losses[-1]:.4f} "
            f"(mesh data={n_dev // model_par} x model={model_par})")
    return losses


def main():
    parser = argparse.ArgumentParser(
        description="model-parallel LSTM LM (sharded gate weights)")
    parser.add_argument("--num-epoch", type=int, default=3)
    parser.add_argument("--batch-size", type=int, default=32)
    parser.add_argument("--hidden", type=int, default=64)
    parser.add_argument("--num-layers", type=int, default=2)
    parser.add_argument("--lr", type=float, default=0.01)
    args = parser.parse_args()
    train(args.num_epoch, args.batch_size, args.hidden, args.num_layers,
          lr=args.lr)


if __name__ == "__main__":
    main()
