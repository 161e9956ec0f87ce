"""Score a trained checkpoint on a validation set — the analog of the
reference's example/image-classification/score.py.

Usage:
  python score.py --model-prefix ckpt/r50 --load-epoch 90 \\
      --data-val val.rec --batch-size 128 [--metrics acc,top_k_accuracy_5]
"""
import argparse

import mxnet_tpu as mx


def score(model_prefix, load_epoch, data_val, image_shape=(3, 224, 224),
          batch_size=128, rgb_mean=(123.68, 116.779, 103.939),
          metrics=("acc",), data_nthreads=4, max_num_batches=None):
    sym, arg_params, aux_params = mx.model.load_checkpoint(
        model_prefix, load_epoch)
    val = mx.io.ImageRecordIter(
        path_imgrec=data_val, data_shape=image_shape,
        batch_size=batch_size, rand_crop=False, rand_mirror=False,
        mean_r=rgb_mean[0], mean_g=rgb_mean[1], mean_b=rgb_mean[2],
        preprocess_threads=data_nthreads)
    if max_num_batches:
        val = mx.io.ResizeIter(val, max_num_batches)
    mod = mx.mod.Module(symbol=sym, context=mx.current_context())
    mod.bind(data_shapes=val.provide_data,
             label_shapes=val.provide_label, for_training=False)
    mod.set_params(arg_params, aux_params)

    def make_metric(m):
        # "top_k_accuracy_5" -> top_k_accuracy with top_k=5
        if m.startswith("top_k_accuracy"):
            suffix = m[len("top_k_accuracy"):].lstrip("_")
            return mx.metric.create("top_k_accuracy",
                                    top_k=int(suffix) if suffix else 5)
        return mx.metric.create(m)

    composite = mx.metric.CompositeEvalMetric(
        [make_metric(m) for m in metrics])
    mod.score(val, composite)  # ONE inference pass for all metrics
    return [m.get() for m in composite.metrics]


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--model-prefix", required=True)
    ap.add_argument("--load-epoch", type=int, required=True)
    ap.add_argument("--data-val", required=True)
    ap.add_argument("--batch-size", type=int, default=128)
    ap.add_argument("--image-shape", default="3,224,224")
    ap.add_argument("--metrics", default="acc")
    ap.add_argument("--data-nthreads", type=int, default=4)
    ap.add_argument("--max-num-batches", type=int, default=None)
    args = ap.parse_args()
    res = score(args.model_prefix, args.load_epoch, args.data_val,
                tuple(int(x) for x in args.image_shape.split(",")),
                args.batch_size, metrics=args.metrics.split(","),
                data_nthreads=args.data_nthreads,
                max_num_batches=args.max_num_batches)
    for name, value in res:
        print(name, value)
