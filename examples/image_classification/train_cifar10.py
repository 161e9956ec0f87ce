"""Train CIFAR-10 (reference: example/image-classification/train_cifar10.py).

    # real data (RecordIO built with tools/im2rec.py)
    python train_cifar10.py --data-train cifar10_train.rec \\
        --data-val cifar10_val.rec

    # synthetic data, no dataset needed: the normal fit() with its Speedometer
    python train_cifar10.py --benchmark 1 --num-epochs 1
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
        description="train cifar10",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    fit.add_fit_args(parser)
    data.add_data_args(parser)
    data.add_aug_args(parser)
    parser.set_defaults(
        network="resnet", num_layers=110,
        num_classes=10, num_examples=50000,
        image_shape="3,32,32",
        batch_size=128, num_epochs=300,
        lr=0.05, lr_step_epochs="200,250", wd=1e-4)
    args = parser.parse_args()

    net = importlib.import_module("symbols." + args.network).get_symbol(
        num_classes=args.num_classes, num_layers=args.num_layers,
        image_shape=args.image_shape)

    fit.fit(args, net, data.get_rec_iter)


if __name__ == "__main__":
    logging.basicConfig(level=logging.INFO)
    main()
