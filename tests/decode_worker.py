"""Subprocess helper for the SIGKILL-mid-decode chaos drill
(test_decode_chaos.py).

Serves a fixed, fully deterministic workload: a pocket transformer LM
(params from ``init_params(seed=0)`` — bit-identical in every process)
behind the continuous batcher, four staggered prompts streaming
through two KV-cache lanes. The token streams are written to the
output file ATOMICALLY (tmp + rename) only after every generation
completed, and the compile registry's ``cache_errors`` total is
printed for the parent to pin.

The parent arms ``MXTPU_FAULT_INJECT=decode_step:token=N:action=kill``
so the kill run SIGKILLs inside the engine's fault consult, mid
continuous-batching step, with generations in flight and the
persistent compile cache already written to. The restarted run must
(a) find no torn compile-cache entry (``cache_errors == 0``) and
(b) re-serve the interrupted prompts to bit-identical streams.

Usage: decode_worker.py <outfile>
"""
import json
import os
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
from mxnet_tpu.serving.decode import (  # noqa: E402
    DecodeBatcher, DecodePredictor, TransformerLMSpec, init_params)


def main():
    outfile = sys.argv[1]
    spec = TransformerLMSpec(vocab_size=64, num_embed=32, num_heads=2,
                             num_layers=2, max_seq=32, name="chaoslm")
    eng = DecodePredictor(spec, init_params(spec, seed=0), slots=2,
                          seq_buckets=(8, 16))
    rng = np.random.RandomState(5)
    prompts = [rng.randint(1, spec.vocab_size, size=n).astype(np.int32)
               for n in (5, 11, 7, 14)]
    streams = []
    with DecodeBatcher(eng, max_wait_us=0, name="chaos") as bat:
        futs = [bat.submit(p, max_new_tokens=8) for p in prompts]
        streams = [f.result(timeout=300) for f in futs]

    rep = mx.compile_report()
    print(f"cache_errors={rep['totals']['cache_errors']} "
          f"fresh_compiles={rep['totals']['fresh_compiles']} "
          f"cache_hits={rep['totals']['cache_hits']}", flush=True)
    tmp = outfile + ".tmp"
    with open(tmp, "w") as f:
        json.dump([[int(t) for t in s] for s in streams], f)
    os.replace(tmp, outfile)
    print("serving complete", flush=True)


if __name__ == "__main__":
    main()
