"""Gluon LSTM language-model training throughput (tokens/sec) on one TPU
chip — the BASELINE.md north-star's second metric (the reference repo
publishes no LSTM tokens/sec figure, so this sets the number to beat).

Model: medium LM (wikitext-2-scale vocab, 650-d embedding + 2x650 LSTM +
tied-size decoder), truncated-BPTT with zero initial state per step (the
standard throughput-benchmark setup). The whole step — embedding, fused
lax.scan LSTM, decoder, softmax CE, backward, SGD update — is ONE XLA
program via parallel.TrainStep, bf16 compute over fp32 master weights.

Usage: python bench_lstm.py [batch] [bptt]
Prints one JSON line: {"metric": "lstm_lm_train_tokens_per_sec", ...}
"""
import json
import sys
import time

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu.gluon import HybridBlock, nn, rnn
from mxnet_tpu.parallel import TrainStep

VOCAB = 33278      # wikitext-2
EMSIZE = 650
NHID = 650
NLAYERS = 2


class LMModel(HybridBlock):
    def __init__(self, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.embed = nn.Embedding(VOCAB, EMSIZE)
            self.lstm = rnn.LSTM(NHID, num_layers=NLAYERS, layout="NTC")
            self.decoder = nn.Dense(VOCAB, flatten=False)

    def hybrid_forward(self, F, x):
        h = self.lstm(self.embed(x))
        out = self.decoder(h)                # (B, T, V)
        return out.reshape((-1, VOCAB))      # (B*T, V)


def main():
    batch = int(sys.argv[1]) if len(sys.argv) > 1 else 512
    bptt = int(sys.argv[2]) if len(sys.argv) > 2 else 35
    steps = 30

    mx.random.seed(0)
    net = LMModel()
    net.initialize(mx.init.Xavier())
    step = TrainStep(net, loss="softmax_ce", optimizer="sgd",
                     optimizer_params={"momentum": 0.9}, lr=0.1,
                     compute_dtype="bfloat16")

    rng = np.random.RandomState(0)
    xs = [mx.nd.array(rng.randint(0, VOCAB, (batch, bptt)), dtype="int32")
          for _ in range(4)]
    ys = [mx.nd.array(rng.randint(0, VOCAB, (batch * bptt,)),
                      dtype="int32") for _ in range(4)]

    loss = None
    for i in range(3):                     # warmup/compile
        loss = step(xs[i % 4], ys[i % 4])
    float(loss.asnumpy())                  # arm real sync

    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for i in range(steps):
            loss = step(xs[i % 4], ys[i % 4])
        loss.wait_to_read()
        best = min(best, time.perf_counter() - t0)
    tok_s = batch * bptt * steps / best
    dev = getattr(loss.data, "device", None) or "cpu"
    print(json.dumps({
        "metric": "lstm_lm_train_tokens_per_sec",
        "value": round(tok_s, 1),
        "unit": "tokens/sec",
        "batch": batch, "bptt": bptt,
        "vocab": VOCAB, "emsize": EMSIZE, "nhid": NHID,
        "nlayers": NLAYERS,
        "step_time_s": round(best / steps, 5),
        "device": str(dev),
    }))


if __name__ == "__main__":
    main()
