#!/usr/bin/env python
"""Train ImageNet classifiers — the north-star entry point.

Reference parity: example/image-classification/train_imagenet.py.
TPU flagship config (BASELINE.md):

    python train_imagenet.py --benchmark 1 --kv-store tpu \
        --network resnet --num-layers 50 --batch-size 128 --dtype bfloat16

Benchmark mode trains on device-resident synthetic batches so the score
is the compute path (Speedometer prints samples/sec); with
--data-train pointing at a RecordIO file it trains for real through
ImageRecordIter.
"""
import argparse
import importlib
import logging
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

from common import data, fit  # noqa: E402


def main(argv=None, **fit_kwargs):
    """Parse ``argv`` (default: the command line) and train; returns
    the fitted Module.  ``fit_kwargs`` reach ``Module.fit`` (e.g. a
    ``batch_end_callback``) — how ``chip_smoke.py`` drives this exact
    path in-process."""
    parser = argparse.ArgumentParser(
        description="train imagenet-1k",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    fit.add_fit_args(parser)
    data.add_data_args(parser)
    data.add_data_aug_args(parser)
    parser.set_defaults(
        network="resnet",
        num_layers=50,
        num_classes=1000,
        num_examples=1281167,
        image_shape="3,224,224",
        min_random_scale=1,
        lr=0.1, lr_factor=0.1, lr_step_epochs="30,60,80",
        num_epochs=1,
        batch_size=128,
    )
    args = parser.parse_args(argv)

    net_module = importlib.import_module("symbols." + args.network)
    sym = net_module.get_symbol(num_classes=args.num_classes,
                                num_layers=args.num_layers,
                                image_shape=args.image_shape,
                                dtype=args.dtype)
    return fit.fit(args, sym, data.get_rec_iter, **fit_kwargs)


if __name__ == "__main__":
    main()
