"""Record the small device trace that tests/bench_harness checks
``trace_reduce`` against, and print what the profiler's planes and lines
look like on this machine.

    python benchmark/testdata/record_small_trace.py <out.xplane.pb>

Three rounds of: a host span that sleeps 5 ms, one jitted ``small_step``
(three 2048x2048 bf16 matmuls), a host span that sleeps 10 ms, one jitted
``other_step`` (an elementwise pass). Run on the chip once (PR 23); the
recorded file is ``small_tpu.xplane.pb`` beside this script.
"""
import glob
import json
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


@jax.jit
def small_step(x):
    for _ in range(3):
        x = jnp.tanh(x @ x) * 0.01
    return x


@jax.jit
def other_step(x):
    return x * 2.0 + 1.0


def main(out):
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    small_step(x).block_until_ready()
    other_step(x).block_until_ready()
    tmp = tempfile.mkdtemp(prefix="small_trace_")
    jax.profiler.start_trace(tmp)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench:host_prepare"):
            time.sleep(0.005)
        with jax.profiler.TraceAnnotation("bench:launch_small"):
            y = small_step(x)
            y.block_until_ready()
        with jax.profiler.TraceAnnotation("bench:host_wait"):
            time.sleep(0.010)
        with jax.profiler.TraceAnnotation("bench:launch_other"):
            other_step(y).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    shutil.copy(path, out)
    print("xplane bytes", os.path.getsize(out))
    pd = jax.profiler.ProfileData.from_file(out)
    for plane in pd.planes:
        print("PLANE", repr(plane.name))
        for line in plane.lines:
            evs = list(line.events)
            print("  LINE", repr(line.name), len(evs))
            for e in evs[:6]:
                stats = {k: str(v)[:60] for k, v in list(e.stats)[:8]}
                print("    ", json.dumps(
                    [e.name[:80], e.start_ns, e.duration_ns, stats]))
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main(sys.argv[1])
