"""Subprocess helper for the kill-and-resume test (test_failure_resume.py).

Trains a tiny symbolic MLP with per-epoch checkpoints. In crash mode the
process SIGKILLs itself right after saving epoch CRASH_AT — simulating a
hard worker failure mid-job (the reference's recovery story is the same:
restart from the last checkpoint; tests/nightly has no in-job elastic
rejoin, and neither does this framework — see docs/faq/failure_recovery.md).

Usage: resume_worker.py <prefix> <num_epoch>
           [--crash-at K | --load-epoch K]
           [--manager-dir D [--auto-resume]]

Two checkpoint regimes:
- legacy: per-epoch ``do_checkpoint`` files + ``--load-epoch`` (the
  reference's recovery story), and
- manager: ``CheckpointManager`` + ``fit(auto_resume=...)`` — full-state
  atomic checkpoints; crashes come from the MXTPU_FAULT_INJECT env spec
  the parent test arms (e.g. SIGKILL at byte N of a checkpoint write).

Writes final train accuracy to <prefix>.acc on clean completion.
"""
import argparse
import logging
import os
import signal
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(_HERE, os.pardir))
sys.path.insert(0, os.path.join(_HERE, os.pardir, "examples",
                                "image_classification"))

import jax  # noqa: E402

# CPU drill: pin the platform BEFORE mxnet_tpu import, in code — a
# worker must stay off a chip its parent may hold, whatever the
# inherited environment says
jax.config.update("jax_platforms", "cpu")

import mxnet_tpu as mx  # noqa: E402


def build_sym(classes=10):
    data = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(mx.sym.Flatten(data), num_hidden=64)
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.FullyConnected(h, num_hidden=classes)
    return mx.sym.SoftmaxOutput(h, name="softmax")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("prefix")
    ap.add_argument("num_epoch", type=int)
    ap.add_argument("--crash-at", type=int, default=None)
    ap.add_argument("--load-epoch", type=int, default=None)
    ap.add_argument("--manager-dir", default=None)
    ap.add_argument("--auto-resume", action="store_true")
    args = ap.parse_args()

    # fit/CheckpointManager report resume + fallback decisions via
    # logging; the parent test asserts on this process's stdout
    logging.basicConfig(level=logging.INFO, stream=sys.stdout, force=True)

    from common.data import SyntheticDataIter
    mx.random.seed(0)
    train = SyntheticDataIter(10, (32, 1, 28, 28), num_batches=20,
                              learnable=True, noise=0.5, seed=0)

    arg_params = aux_params = None
    begin_epoch = 0
    if args.load_epoch is not None:
        _, arg_params, aux_params = mx.model.load_checkpoint(
            args.prefix, args.load_epoch)
        begin_epoch = args.load_epoch
        print(f"Resume training from epoch {begin_epoch}", flush=True)

    manager = None
    cbs = []
    if args.manager_dir is not None:
        manager = mx.CheckpointManager(args.manager_dir)
    else:
        cbs.append(mx.callback.do_checkpoint(args.prefix))
    if args.crash_at is not None:
        crash_at = args.crash_at

        def _crash(epoch, sym, arg, aux):
            if epoch + 1 >= crash_at:  # after the checkpoint for this epoch
                print(f"simulating hard failure after epoch {epoch + 1}",
                      flush=True)
                os.kill(os.getpid(), signal.SIGKILL)
        cbs.append(_crash)

    mod = mx.mod.Module(symbol=build_sym(), context=mx.cpu())
    mod.fit(train, num_epoch=args.num_epoch, optimizer="sgd",
            optimizer_params={"learning_rate": 0.05},
            initializer=mx.init.Xavier(), eval_metric="acc",
            arg_params=arg_params, aux_params=aux_params,
            begin_epoch=begin_epoch,
            epoch_end_callback=cbs or None,
            checkpoint_manager=manager, auto_resume=args.auto_resume)

    train.reset()
    acc = mod.score(train, "acc")[0][1]
    with open(args.prefix + ".acc", "w") as f:
        f.write(str(acc))
    print("final acc", acc, flush=True)


if __name__ == "__main__":
    main()
