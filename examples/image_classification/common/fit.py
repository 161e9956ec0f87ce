"""The shared training driver: argparse → Module.fit.

Capability rebuild of the reference's example/image-classification/common/
fit.py:141 (``fit(args, network, data_loader)``): wires the kvstore, LR
schedule, initializer, checkpointing and monitoring around Module.fit. On
TPU the device list collapses into the GSPMD mesh — ``--gpus 0,1,..`` is
kept as a flag and maps to "shard the batch this many ways".
"""
from __future__ import annotations

import argparse
import logging
import os
import re
import time

import mxnet_tpu as mx


def add_fit_args(parser: argparse.ArgumentParser):
    """(reference: common/fit.py:58 add_fit_args)"""
    train = parser.add_argument_group("Training", "model training")
    train.add_argument("--network", type=str, help="the neural network to use")
    train.add_argument("--num-layers", type=int,
                       help="number of layers in the neural network")
    train.add_argument("--gpus", type=str, default=None,
                       help="devices to run on; e.g. '0,1'. On TPU this "
                       "selects how many mesh devices shard the batch")
    train.add_argument("--kv-store", type=str, default="device",
                       help="key-value store type")
    train.add_argument("--num-epochs", type=int, default=100)
    train.add_argument("--lr", type=float, default=0.1)
    train.add_argument("--lr-factor", type=float, default=0.1,
                       help="reduce the lr by this factor at each step")
    train.add_argument("--lr-step-epochs", type=str, default="30,60",
                       help="epochs at which the lr decays")
    train.add_argument("--optimizer", type=str, default="sgd")
    train.add_argument("--mom", type=float, default=0.9)
    train.add_argument("--wd", type=float, default=1e-4)
    train.add_argument("--batch-size", type=int, default=128)
    train.add_argument("--disp-batches", type=int, default=20,
                       help="show progress every N batches")
    train.add_argument("--model-prefix", type=str,
                       help="checkpoint prefix (save + resume)")
    train.add_argument("--load-epoch", type=int,
                       help="load the model saved at this epoch")
    train.add_argument("--top-k", type=int, default=0,
                       help="also report top-k accuracy")
    train.add_argument("--dtype", type=str, default="float32",
                       help="compute precision: float32 or bfloat16")
    train.add_argument("--monitor", type=int, default=0,
                       help="log network statistics every N batches")
    train.add_argument("--test-io", type=int, default=0,
                       help="only test the data pipeline speed")
    return train


def _get_lr_scheduler(args, kv, epoch_size):
    """(reference: common/fit.py:30 _get_lr_scheduler)"""
    if not args.lr_factor or args.lr_factor >= 1:
        return args.lr, None
    begin_epoch = args.load_epoch or 0
    step_epochs = [int(l) for l in args.lr_step_epochs.split(",") if l]
    lr = args.lr
    for s in step_epochs:
        if begin_epoch >= s:
            lr *= args.lr_factor
    if lr != args.lr:
        logging.info("Adjust learning rate to %e for epoch %d", lr,
                     begin_epoch)
    steps = [epoch_size * (x - begin_epoch) for x in step_epochs
             if x - begin_epoch > 0]
    if not steps:
        return lr, None
    return lr, mx.lr_scheduler.MultiFactorScheduler(step=steps,
                                                    factor=args.lr_factor)


def _load_model(args, rank=0):
    if args.load_epoch is None or args.model_prefix is None:
        return None, None, None
    model_prefix = args.model_prefix
    sym, arg_params, aux_params = mx.model.load_checkpoint(
        model_prefix, args.load_epoch)
    logging.info("Loaded model %s_%04d.params", model_prefix,
                 args.load_epoch)
    return sym, arg_params, aux_params


def _save_model(args, rank=0):
    if args.model_prefix is None:
        return None
    dst_dir = os.path.dirname(args.model_prefix)
    if dst_dir and not os.path.isdir(dst_dir):
        os.makedirs(dst_dir, exist_ok=True)
    return mx.callback.do_checkpoint(
        args.model_prefix if rank == 0
        else "%s-%d" % (args.model_prefix, rank))


def fit(args, network, data_loader, **kwargs):
    """Train ``network`` (a Symbol) on the iterators from ``data_loader``
    (reference: common/fit.py:141)."""
    kv = mx.kvstore.create(args.kv_store)

    head = "%(asctime)-15s Node[" + str(kv.rank) + "] %(message)s"
    logging.basicConfig(level=logging.INFO, format=head)
    logging.info("start with arguments %s", args)

    train, val = data_loader(args, kv)

    if args.test_io:
        tic = time.time()
        for i, batch in enumerate(train):
            for j in batch.data:
                j.wait_to_read()
            if (i + 1) % args.disp_batches == 0:
                logging.info("Batch [%d]\tSpeed: %.2f samples/sec", i,
                             args.disp_batches * args.batch_size /
                             (time.time() - tic))
                tic = time.time()
        return

    sym, arg_params, aux_params = _load_model(args, kv.rank)
    if sym is not None:
        assert sym.tojson() == network.tojson()
    # caller-provided warm-start params (fine_tune.py) take precedence
    # over checkpoint loading; both can't be active at once. Always pop:
    # leftovers would collide with the explicit keywords at model.fit.
    caller_arg = kwargs.pop("arg_params", None)
    caller_aux = kwargs.pop("aux_params", None)
    if caller_arg is not None or caller_aux is not None:
        assert arg_params is None and aux_params is None, \
            "pass either --load-epoch or explicit arg/aux_params, not both"
        arg_params, aux_params = caller_arg, caller_aux

    checkpoint = _save_model(args, kv.rank)

    devs = mx.cpu() if args.gpus is None or args.gpus == "" else [
        mx.gpu(int(i)) for i in args.gpus.split(",")]

    epoch_size = args.num_examples // args.batch_size
    lr, lr_scheduler = _get_lr_scheduler(args, kv, epoch_size)

    model = mx.mod.Module(context=devs, symbol=network)

    optimizer_params = {
        "learning_rate": lr,
        "wd": args.wd,
        "lr_scheduler": lr_scheduler}
    if args.optimizer in ("sgd", "nag", "signum", "lbsgd"):
        optimizer_params["momentum"] = args.mom
    # bf16 compute with fp32 master weights (the reference's fp16 path
    # uses multi_precision the same way, fit.py dtype handling)
    if args.dtype == "bfloat16":
        optimizer_params["multi_precision"] = True

    initializer = mx.init.Xavier(rnd_type="gaussian", factor_type="in",
                                 magnitude=2)

    eval_metrics = ["accuracy"]
    if args.top_k > 0:
        eval_metrics.append(mx.metric.create("top_k_accuracy",
                                             top_k=args.top_k))

    batch_end_callbacks = [mx.callback.Speedometer(args.batch_size,
                                                   args.disp_batches)]

    monitor = mx.mon.Monitor(args.monitor, pattern=".*") \
        if args.monitor > 0 else None

    model.fit(train,
              begin_epoch=args.load_epoch if args.load_epoch else 0,
              num_epoch=args.num_epochs,
              eval_data=val,
              eval_metric=eval_metrics,
              kvstore=kv,
              optimizer=args.optimizer,
              optimizer_params=optimizer_params,
              initializer=initializer,
              arg_params=arg_params,
              aux_params=aux_params,
              batch_end_callback=batch_end_callbacks,
              epoch_end_callback=checkpoint,
              allow_missing=True,
              monitor=monitor,
              **kwargs)
    return model
