"""Shared fit() harness (reference: example/image-classification/common/
fit.py:148 — arg groups, kvstore setup, lr schedule, Module.fit)."""

import argparse
import logging
import time

import mxnet_tpu as mx


def add_fit_args(parser):
    train = parser.add_argument_group("Training")
    train.add_argument("--network", type=str, default="resnet50_v1")
    train.add_argument("--num-layers", type=int, default=50)
    train.add_argument("--num-classes", type=int, default=1000)
    train.add_argument("--batch-size", type=int, default=128)
    train.add_argument("--num-epochs", type=int, default=80)
    train.add_argument("--lr", type=float, default=0.1)
    train.add_argument("--lr-factor", type=float, default=0.1)
    train.add_argument("--lr-step-epochs", type=str, default="30,60,90")
    train.add_argument("--optimizer", type=str, default="sgd")
    train.add_argument("--mom", type=float, default=0.9)
    train.add_argument("--wd", type=float, default=1e-4)
    train.add_argument("--kv-store", type=str, default="tpu")
    train.add_argument("--disp-batches", type=int, default=20)
    train.add_argument("--model-prefix", type=str, default=None)
    train.add_argument("--load-epoch", type=int, default=None)
    train.add_argument("--dtype", type=str, default="float32")
    train.add_argument("--monitor", type=int, default=0)
    return train


def _lr_scheduler(args, kv, epoch_size, begin_epoch):
    steps = [int(x) for x in args.lr_step_epochs.split(",") if x]
    lr = args.lr
    for s in steps:
        if begin_epoch >= s:
            lr *= args.lr_factor
    # strictly-future steps only: a step exactly at begin_epoch is already
    # folded into lr above (reference: common/fit.py _get_lr_scheduler)
    factor_steps = [epoch_size * (s - begin_epoch) for s in steps
                    if s > begin_epoch]
    sched = mx.lr_scheduler.MultiFactorScheduler(
        step=factor_steps, factor=args.lr_factor) if factor_steps else None
    return lr, sched


def fit(args, network, data_loader, **kwargs):
    """Train `network` (a Symbol) with the Module API (reference:
    common/fit.py fit)."""
    kv = mx.kv.create(args.kv_store)
    logging.basicConfig(
        level=logging.INFO,
        format="Node[%d] %%(asctime)s %%(message)s" % kv.rank)
    train, val = data_loader(args, kv)

    epoch_size = args.num_examples // args.batch_size // max(kv.num_workers, 1)
    begin_epoch = args.load_epoch or 0
    lr, lr_sched = _lr_scheduler(args, kv, max(epoch_size, 1), begin_epoch)

    mod = mx.mod.Module(symbol=network, context=_contexts(),
                        label_names=("softmax_label",))
    optimizer_params = {"learning_rate": lr, "wd": args.wd}
    if args.optimizer in ("sgd", "nag", "signum"):
        optimizer_params["momentum"] = args.mom
    if lr_sched is not None:
        optimizer_params["lr_scheduler"] = lr_sched

    arg_params = aux_params = None
    if args.model_prefix and args.load_epoch is not None:
        _, arg_params, aux_params = mx.model.load_checkpoint(
            args.model_prefix, args.load_epoch)

    checkpoint = mx.callback.do_checkpoint(args.model_prefix) \
        if args.model_prefix else None
    batch_cb = mx.callback.Speedometer(args.batch_size, args.disp_batches)

    mod.fit(train,
            eval_data=val,
            eval_metric=["accuracy"],
            begin_epoch=begin_epoch,
            num_epoch=args.num_epochs,
            optimizer=args.optimizer,
            optimizer_params=optimizer_params,
            kvstore=kv,
            arg_params=arg_params,
            aux_params=aux_params,
            batch_end_callback=batch_cb,
            epoch_end_callback=checkpoint,
            **kwargs)
    return mod


def _contexts():
    return [mx.current_context()]


def get_network(name, num_classes, image_shape):
    """Build a model-zoo network as a Symbol (reference builds symbols
    from symbols/<net>.py; here the Gluon zoo is traced)."""
    from mxnet_tpu.contrib.quantization import _trace_block
    from mxnet_tpu.gluon.model_zoo import vision

    net = getattr(vision, name)(classes=num_classes)
    net.initialize()
    data = mx.sym.Variable("data")
    sym, _ = _trace_block(net, [data], [(1,) + tuple(image_shape)])
    label = mx.sym.Variable("softmax_label")
    return mx.sym.SoftmaxOutput(sym, label, name="softmax")
