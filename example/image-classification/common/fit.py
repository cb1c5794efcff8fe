"""The shared Module.fit driver for the training scripts.

Reference parity: example/image-classification/common/fit.py (fit:149 —
kvstore creation, lr schedule, checkpointing, Speedometer, Module.fit).
TPU notes: kvstore 'tpu' routes parameter updates through the fused
one-dispatch update store (mxnet_tpu.kvstore.KVStoreTPU); --dtype
bfloat16 turns on the executor mixed-precision policy.
"""
import argparse
import logging
import os
import re
import time

import mxnet_tpu as mx


def add_fit_args(parser):
    train = parser.add_argument_group("Training", "model training")
    train.add_argument("--network", type=str, help="the neural network to use")
    train.add_argument("--num-layers", type=int,
                       help="number of layers in the neural network")
    train.add_argument("--gpus", type=str, default="",
                       help="unused on TPU; kept for reference CLI parity")
    train.add_argument("--kv-store", type=str, default="tpu",
                       help="key-value store type (tpu/local/device/dist_sync)")
    train.add_argument("--num-epochs", type=int, default=90,
                       help="max num of epochs")
    train.add_argument("--lr", type=float, default=0.1,
                       help="initial learning rate")
    train.add_argument("--lr-factor", type=float, default=0.1,
                       help="the ratio to reduce lr on each step")
    train.add_argument("--lr-step-epochs", type=str, default="30,60,80",
                       help="the epochs to reduce the lr, e.g. 30,60")
    train.add_argument("--optimizer", type=str, default="sgd",
                       help="the optimizer type")
    train.add_argument("--mom", type=float, default=0.9, help="momentum")
    train.add_argument("--wd", type=float, default=0.0001,
                       help="weight decay")
    train.add_argument("--batch-size", type=int, default=128,
                       help="the batch size")
    train.add_argument("--disp-batches", type=int, default=20,
                       help="show progress for every n batches")
    train.add_argument("--model-prefix", type=str,
                       help="model checkpoint prefix")
    train.add_argument("--load-epoch", type=int,
                       help="load the model on an epoch using model-prefix")
    train.add_argument("--top-k", type=int, default=0,
                       help="report the top-k accuracy. 0 = no report")
    train.add_argument("--test-io", type=int, default=0,
                       help="1 = test reading speed without training")
    train.add_argument("--num-batches", type=int, default=100,
                       help="number of batches per epoch in benchmark mode")
    train.add_argument("--monitor", type=int, default=0,
                       help="log network parameter stats every N batches")
    return train


def _get_lr_scheduler(args, kv):
    if not args.lr_factor or args.lr_factor >= 1:
        return (args.lr, None)
    epoch_size = _epoch_size(args, kv)
    begin_epoch = args.load_epoch if args.load_epoch else 0
    step_epochs = [int(l) for l in args.lr_step_epochs.split(",") if l]
    lr = args.lr
    for s in step_epochs:
        if begin_epoch >= s:
            lr *= args.lr_factor
    if lr != args.lr:
        logging.info("Adjust learning rate to %e for epoch %d", lr, begin_epoch)
    steps = [epoch_size * (x - begin_epoch) for x in step_epochs
             if x - begin_epoch > 0]
    if not steps:
        return (lr, None)
    return (lr, mx.lr_scheduler.MultiFactorScheduler(step=steps,
                                                     factor=args.lr_factor))


def _epoch_size(args, kv):
    return max(int(args.num_examples / args.batch_size / kv.num_workers), 1)


def _load_model(args, rank=0):
    if args.load_epoch is None or args.model_prefix is None:
        return (None, None, None)
    model_prefix = args.model_prefix
    if rank > 0 and os.path.exists("%s-%d-symbol.json" % (model_prefix, rank)):
        model_prefix += "-%d" % rank
    sym, arg_params, aux_params = mx.model.load_checkpoint(
        model_prefix, args.load_epoch)
    logging.info("Loaded model %s_%04d.params", model_prefix, args.load_epoch)
    return (sym, arg_params, aux_params)


def _save_model(args, rank=0):
    if args.model_prefix is None:
        return None
    dst_dir = os.path.dirname(args.model_prefix)
    if dst_dir and not os.path.isdir(dst_dir):
        os.makedirs(dst_dir, exist_ok=True)
    prefix = args.model_prefix if rank == 0 else "%s-%d" % (args.model_prefix,
                                                            rank)
    return mx.callback.do_checkpoint(prefix)


def fit(args, network, data_loader, **kwargs):
    """Train the network (reference: common/fit.py fit)."""
    kv = mx.kvstore.create(args.kv_store)

    head = "%(asctime)-15s Node[" + str(kv.rank) + "] %(message)s"
    logging.basicConfig(level=logging.INFO, format=head)
    logging.info("start with arguments %s", args)
    # what jax gave this process: mx.tpu() below names a host device
    # when there is no accelerator (how the CPU tests run), so a log
    # with a speed in it says which platform the speed is of
    import jax
    devs = jax.devices()
    logging.info("device platform=%s kind=%s count=%d", devs[0].platform,
                 devs[0].device_kind, len(devs))

    (train, val) = data_loader(args, kv)
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

    lr, lr_scheduler = _get_lr_scheduler(args, kv)

    dtype = getattr(args, "dtype", "float32")
    compute_dtype = None if dtype in ("float32", "fp32") else dtype
    model = mx.mod.Module(context=mx.tpu() if mx.num_tpus() else mx.cpu(),
                          symbol=network, compute_dtype=compute_dtype)

    optimizer_params = {
        "learning_rate": lr,
        "wd": args.wd,
        "lr_scheduler": lr_scheduler}
    if args.optimizer in ("sgd", "nag", "signum", "lbsgd"):
        optimizer_params["momentum"] = args.mom

    monitor = mx.monitor.Monitor(args.monitor, pattern=".*") \
        if args.monitor > 0 else None

    initializer = mx.init.Xavier(rnd_type="gaussian", factor_type="in",
                                 magnitude=2)

    eval_metrics = ["accuracy"]
    if args.top_k > 0:
        eval_metrics.append(mx.metric.create("top_k_accuracy",
                                             top_k=args.top_k))

    batch_end_callbacks = [mx.callback.Speedometer(args.batch_size,
                                                   args.disp_batches)]
    if "batch_end_callback" in kwargs:
        cbs = kwargs.pop("batch_end_callback")
        batch_end_callbacks += cbs if isinstance(cbs, list) else [cbs]

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
              epoch_end_callback=_save_model(args, kv.rank),
              allow_missing=True,
              monitor=monitor,
              **kwargs)
    return model
