"""Train ImageNet-scale image classification (reference:
example/image-classification/train_imagenet.py:58).

    # real data (RecordIO built with tools/im2rec.py)
    python train_imagenet.py --network resnet --num-layers 50 \
        --data-train train.rec --data-val val.rec

    # synthetic data, no dataset needed: the normal fit() with its Speedometer
    python train_imagenet.py --network resnet --num-layers 50 \
        --benchmark 1 --num-epochs 1 --dtype bfloat16
"""
import argparse
import importlib
import logging
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))

from common import data, fit


def main():
    parser = argparse.ArgumentParser(
        description="train imagenet-1k",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    fit.add_fit_args(parser)
    data.add_data_args(parser)
    data.add_aug_args(parser)
    parser.set_defaults(
        network="resnet", num_layers=50,
        num_classes=1000, num_examples=1281167,
        image_shape="3,224,224",
        batch_size=128, num_epochs=80,
        lr=0.1, lr_step_epochs="30,60,80", wd=1e-4)
    args = parser.parse_args()

    net = importlib.import_module("symbols." + args.network).get_symbol(
        num_classes=args.num_classes, num_layers=args.num_layers,
        image_shape=args.image_shape)

    fit.fit(args, net, data.get_rec_iter)


if __name__ == "__main__":
    logging.basicConfig(level=logging.INFO)
    main()
