"""Sparse linear-regression end-to-end benchmark with phase breakdown.

Parity: /root/reference/benchmark/python/sparse/sparse_end2end.py (the
BASELINE.md measurement-tools row "sparse op + end-to-end benchmarks").
Same shape: LibSVM data through a sparse embedding/dot linear model with a
row_sparse weight pushed/pulled through a kvstore, measuring total
samples/sec plus what the reference's --measure-only io/compute/
communication split reports — here as per-phase timings taken in one run
(io = iterator next, comm = kvstore push/pull + row_sparse_pull,
compute = forward/backward/update minus comm).

One JSON line:

    {"metric": "sparse_linear_samples_per_sec", "value": ..., "io_ms": ...,
     "comm_ms": ..., "compute_ms": ...}

Usage: python tools/sparse_end2end.py [--num-features 100000] [--nnz 30]
       [--batch-size 512] [--num-batch 50] [--kvstore local]
       [--platform cpu]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def make_libsvm(path, n, dim, nnz, seed=0):
    import numpy as np
    rng = np.random.RandomState(seed)
    w = rng.randn(dim)
    with open(path, "w") as f:
        for _ in range(n):
            idx = rng.choice(dim, min(nnz, dim), replace=False)
            val = rng.randn(len(idx))
            y = float(np.dot(w[idx], val))
            f.write("%.4f %s\n" % (y, " ".join(
                "%d:%.4f" % (i, v) for i, v in sorted(zip(idx, val)))))
    return path


def main():
    p = argparse.ArgumentParser(
        formatter_class=argparse.ArgumentDefaultsHelpFormatter,
        description="sparse linear regression end-to-end benchmark")
    p.add_argument("--num-features", type=int, default=100000)
    p.add_argument("--nnz", type=int, default=30,
                   help="non-zeros per example")
    p.add_argument("--batch-size", type=int, default=512)
    p.add_argument("--num-batch", type=int, default=50)
    p.add_argument("--num-epoch", type=int, default=2,
                   help="epoch 0 warms compiles; later epochs are timed")
    p.add_argument("--kvstore", default="local")
    p.add_argument("--platform", default=None, choices=[None, "cpu"])
    args = p.parse_args()
    if args.num_epoch < 2:
        p.error("--num-epoch must be >= 2 (epoch 0 is compile warmup; "
                "timing starts at epoch 1)")
    if args.platform == "cpu":
        import jax
        jax.config.update("jax_platforms", "cpu")

    import numpy as np
    import jax
    import mxnet_tpu as mx

    n_examples = args.batch_size * args.num_batch
    path = make_libsvm(os.path.join(tempfile.gettempdir(),
                                    "mxtpu_sparse_e2e.libsvm"), n_examples,
                       args.num_features, args.nnz)

    kv = mx.kv.create(args.kvstore)
    it = mx.io.LibSVMIter(data_libsvm=path,
                          data_shape=(args.num_features,),
                          batch_size=args.batch_size)

    on_tpu = jax.devices()[0].platform != "cpu"
    ctx = mx.tpu() if on_tpu else mx.cpu()
    weight = mx.nd.sparse.zeros("row_sparse", (args.num_features, 1))
    kv.init("w", weight)
    optimizer = mx.optimizer.create("adagrad", learning_rate=0.1)
    kv.set_optimizer(optimizer)

    io_s = comm_s = 0.0
    t_total0 = None
    n_seen = 0
    for epoch in range(args.num_epoch):
        it.reset()
        if epoch == 1:
            t_total0 = time.perf_counter()
            io_s = comm_s = 0.0
        while True:
            t0 = time.perf_counter()
            try:
                batch = next(it)
            except StopIteration:
                break
            io_s += time.perf_counter() - t0

            csr = batch.data[0]
            row_ids = mx.nd.array(
                np.unique(csr.indices.asnumpy()), dtype="int64")
            t0 = time.perf_counter()
            kv.row_sparse_pull("w", out=weight, row_ids=row_ids)
            comm_s += time.perf_counter() - t0

            # forward/backward by hand: pred = X.w ; grad = X^T (pred - y)/b
            pred = mx.nd.sparse.dot(csr, weight)
            err = pred - batch.label[0].reshape((-1, 1))
            grad_dense = mx.nd.sparse.dot(csr, err / args.batch_size,
                                          transpose_a=True)
            grad = grad_dense.tostype("row_sparse")

            t0 = time.perf_counter()
            kv.push("w", grad)
            comm_s += time.perf_counter() - t0
            if epoch > 0:
                n_seen += args.batch_size
    mx.nd.waitall()
    total = time.perf_counter() - t_total0
    compute = max(total - io_s - comm_s, 0.0)
    timed_batches = args.num_batch * (args.num_epoch - 1)
    print(json.dumps({
        "metric": "sparse_linear_samples_per_sec",
        "value": round(n_seen / total, 1), "unit": "samples/s",
        "num_features": args.num_features, "batch": args.batch_size,
        "kvstore": args.kvstore,
        "io_ms": round(io_s / timed_batches * 1e3, 2),
        "comm_ms": round(comm_s / timed_batches * 1e3, 2),
        "compute_ms": round(compute / timed_batches * 1e3, 2),
        "device": jax.devices()[0].device_kind,
    }))


if __name__ == "__main__":
    main()
