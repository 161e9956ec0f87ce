"""Subprocess helper for the data-pipeline chaos test
(test_data_pipeline.py::test_mid_epoch_sigkill_and_resume).

Streams batches from a RecordIO-backed DataPipeline, appending one CRC32
line per consumed batch to ``<dir>/<log>`` and checkpointing the
pipeline cursor through a real ``CheckpointManager`` after every batch.
The parent arms ``MXTPU_FAULT_INJECT=data_worker:batch=K:action=kill``
so a decode WORKER THREAD SIGKILLs the process mid-epoch; the resume run
loads the newest valid checkpoint, ``set_state``s the pipeline, and
streams the remaining batches — the parent asserts the resumed stream
equals the uninterrupted run's tail exactly (no skipped or duplicated
batch relative to the checkpoint cursor).

Usage: data_pipeline_worker.py <dir> <log> [--resume] [--ref]
"""
import argparse
import os
import sys
import zlib

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(_HERE, os.pardir))

import jax  # noqa: E402

# CPU drill: pin the platform BEFORE mxnet_tpu import, in code — a
# worker must stay off a chip its parent may hold, whatever the
# inherited environment says
jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu.data import from_recordio  # noqa: E402

DATA_SHAPE = (2, 4, 4)
BATCH = 4
SEED = 5


def build_rec(path_rec):
    """80 deterministic records -> 20 batches/epoch (idempotent).

    Sized so the armed kill ordinal (batch=16) sits BEYOND the
    pipeline's maximum read-ahead of the consumer (~9 batches with
    queue_depth=1/stage_ahead=1/2 workers): by the time any worker can
    reach the kill, the consumer has durably committed several
    checkpoints — the drill is deterministic, never a no-valid-
    checkpoint coin flip."""
    from mxnet_tpu import recordio
    if os.path.exists(path_rec):
        return
    idx = os.path.splitext(path_rec)[0] + ".idx"
    w = recordio.MXIndexedRecordIO(idx, path_rec, "w")
    rng = np.random.RandomState(0)
    for i in range(80):
        arr = rng.rand(*DATA_SHAPE).astype(np.float32)
        w.write_idx(i, recordio.pack(
            recordio.IRHeader(0, float(i), i, 0), arr.tobytes()))
    w.close()


def crc_line(batch):
    crc = zlib.crc32(np.ascontiguousarray(batch.data[0].asnumpy()).tobytes())
    crc = zlib.crc32(np.ascontiguousarray(batch.label[0].asnumpy())
                     .tobytes(), crc)
    return f"{crc:08x}"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("dir")
    ap.add_argument("log")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--ref", action="store_true",
                    help="uninterrupted reference run, no checkpoints")
    args = ap.parse_args()

    rec = os.path.join(args.dir, "chaos.rec")
    build_rec(rec)
    # shallow queues: the stream runs at most a few batches ahead of the
    # consumer, so the armed worker kill lands AFTER checkpoints exist
    pipe = from_recordio(rec, DATA_SHAPE, BATCH, shuffle=True, seed=SEED,
                         num_workers=2, queue_depth=1, stage_ahead=1,
                         name="chaos")
    manager = None
    if not args.ref:
        manager = mx.CheckpointManager(os.path.join(args.dir, "ck"),
                                       keep=2, async_save=False)
    if args.resume:
        state = manager.load_latest()
        assert state is not None, "no valid checkpoint to resume from"
        ds = state.data_state
        assert ds is not None, "checkpoint carries no data cursor"
        pipe.set_state(ds)
        print(f"resumed at batch {ds['batch']}", flush=True)

    log = open(os.path.join(args.dir, args.log), "a")
    seq = 0
    import time
    for batch in pipe:
        log.write(crc_line(batch) + "\n")
        log.flush()
        os.fsync(log.fileno())
        if manager is not None:
            seq += 1
            # a real full-state checkpoint: tiny params + the pipeline
            # cursor riding in extra (what fit's epoch-end save does)
            manager.save_state(
                {"w": np.zeros(2, np.float32)}, {},
                meta={"tag": seq, "epoch": 0, "nbatch": seq},
                payload={"extra": {"data_state": pipe.get_state()}})
        if not args.ref:
            time.sleep(0.05)   # slow consumer: the pipeline runs ahead,
            #                    so the armed worker kill lands mid-epoch
    pipe.close()
    log.close()
    print("stream complete", flush=True)


if __name__ == "__main__":
    main()
