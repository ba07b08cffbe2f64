#!/usr/bin/env python
"""ImageNet-scale classification training CLI (reference
example/image-classification/train_imagenet.py workflow): RecordIO data
via the threaded ImageRecordIter, model-zoo symbols, Module.fit with the
fused tpu_sync step, multi-precision bf16, checkpointing, and the
reference's --benchmark 1 mode (one synthetic device-resident batch,
throughput printed).

    python train_imagenet.py --benchmark 1 --network resnet --num-layers 50
    python train_imagenet.py --data-train train.rec --network inception-v3
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from _common import maybe_force_cpu, pick_ctx  # noqa: E402
maybe_force_cpu()

import logging
logging.basicConfig(level=logging.INFO)

import numpy as np
import mxnet_tpu as mx


def build_symbol(args):
    from mxnet_tpu import models
    if args.network == "resnet":
        return models.resnet_symbol(num_classes=args.num_classes,
                                    num_layers=args.num_layers,
                                    image_shape=args.image_shape)
    if args.network == "inception-v3":
        return models.inception_v3_symbol(num_classes=args.num_classes)
    if args.network == "alexnet":
        return models.alexnet_symbol(num_classes=args.num_classes)
    raise SystemExit("unknown --network %r" % args.network)


class _OneBatchIter:
    """--benchmark 1: one device-resident synthetic batch, repeated."""

    def __init__(self, batch, steps, provide_data, provide_label):
        self._batch, self._steps = batch, steps
        self.provide_data, self.provide_label = provide_data, provide_label
        self.batch_size = provide_data[0].shape[0]
        self._i = 0

    def __iter__(self):
        return self

    def __next__(self):
        if self._i >= self._steps:
            raise StopIteration
        self._i += 1
        return self._batch

    def reset(self):
        self._i = 0


def get_data(args, ctx):
    shp = tuple(int(x) for x in args.image_shape.split(","))
    if args.benchmark:
        from mxnet_tpu.io import DataBatch, DataDesc
        rng = np.random.RandomState(0)
        data = mx.nd.array(rng.randn(args.batch_size, *shp)
                           .astype(np.float32), ctx=ctx)
        label = mx.nd.array(rng.randint(0, args.num_classes,
                                        (args.batch_size,))
                            .astype(np.float32), ctx=ctx)
        it = _OneBatchIter(
            DataBatch(data=[data], label=[label]), args.benchmark_steps,
            [DataDesc("data", (args.batch_size,) + shp)],
            [DataDesc("softmax_label", (args.batch_size,))])
        return it, None
    if not args.data_train:
        raise SystemExit("--data-train is required unless --benchmark 1")
    from mxnet_tpu.io import ImageRecordIter
    train = ImageRecordIter(
        args.data_train, data_shape=shp, batch_size=args.batch_size,
        rand_crop=True, rand_mirror=True,
        preprocess_threads=args.data_nthreads, shuffle=True, ctx=ctx)
    val = None
    if args.data_val:
        val = ImageRecordIter(
            args.data_val, data_shape=shp, batch_size=args.batch_size,
            preprocess_threads=args.data_nthreads, ctx=ctx)
    return train, val


def main():
    p = argparse.ArgumentParser(
        description="train on imagenet-shaped data",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    p.add_argument("--network", default="resnet")
    p.add_argument("--num-layers", type=int, default=50)
    p.add_argument("--num-classes", type=int, default=1000)
    p.add_argument("--image-shape", default="3,224,224")
    p.add_argument("--data-train", default=None)
    p.add_argument("--data-val", default=None)
    p.add_argument("--data-nthreads", type=int, default=4)
    p.add_argument("--batch-size", type=int, default=128)
    p.add_argument("--num-epochs", type=int, default=1)
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--mom", type=float, default=0.9)
    p.add_argument("--wd", type=float, default=1e-4)
    p.add_argument("--lr-step-epochs", default=None,
                   help="e.g. 30,60 (FactorScheduler 0.1)")
    p.add_argument("--kv-store", default="tpu_sync")
    p.add_argument("--multi-precision", type=int, default=1,
                   help="bf16 compute over f32 master weights")
    p.add_argument("--benchmark", type=int, default=0)
    p.add_argument("--benchmark-steps", type=int, default=30)
    p.add_argument("--model-prefix", default=None)
    p.add_argument("--load-epoch", type=int, default=None)
    p.add_argument("--disp-batches", type=int, default=20)
    p.add_argument("--device", default=None)
    args = p.parse_args()

    ctx = pick_ctx()
    train, val = get_data(args, ctx)
    sym = build_symbol(args)

    opt_params = {"learning_rate": args.lr, "momentum": args.mom,
                  "wd": args.wd, "multi_precision": bool(args.multi_precision)}
    if args.lr_step_epochs and not args.benchmark:
        steps_per_epoch = max(1, getattr(train, "num_batches", 1000))
        opt_params["lr_scheduler"] = mx.lr_scheduler.MultiFactorScheduler(
            [int(e) * steps_per_epoch
             for e in args.lr_step_epochs.split(",")], factor=0.1)

    mod = mx.mod.Module(sym, context=ctx)
    arg_p = aux_p = None
    if args.model_prefix and args.load_epoch is not None:
        _, arg_p, aux_p = mx.model.load_checkpoint(args.model_prefix,
                                                   args.load_epoch)

    cbs = [mx.callback.Speedometer(args.batch_size, args.disp_batches)]
    ep_cbs = []
    if args.model_prefix:
        ep_cbs.append(mx.callback.do_checkpoint(args.model_prefix))

    times = []
    if args.benchmark:
        def bench_cb(epoch, symbol, a, b):
            import jax as _j
            _j.device_get(mod._exec.arg_dict[mod._param_names[0]]._data)
            times.append(time.perf_counter())
        ep_cbs.append(bench_cb)

    mod.fit(train, eval_data=val,
            num_epoch=3 if args.benchmark else args.num_epochs,
            eval_metric=None if args.benchmark else "acc",
            kvstore=args.kv_store, optimizer="sgd",
            optimizer_params=opt_params,
            initializer=mx.initializer.Xavier(rnd_type="gaussian",
                                              factor_type="in",
                                              magnitude=2),
            arg_params=arg_p, aux_params=aux_p,
            begin_epoch=args.load_epoch or 0,
            batch_end_callback=None if args.benchmark else cbs,
            epoch_end_callback=ep_cbs)

    if args.benchmark and len(times) >= 2:
        import jax
        dt = times[-1] - times[0]
        n = args.benchmark_steps * (len(times) - 1)
        print("benchmark: %.2f img/s (batch %d, %s)"
              % (args.batch_size * n / dt, args.batch_size,
                 jax.devices()[0].device_kind))


if __name__ == "__main__":
    main()
