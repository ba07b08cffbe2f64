"""Per-layer conv microbenchmarks: is the MXU actually fast on our convs?

Times representative ResNet-50 conv shapes (fwd only, bf16, batch 128) in
isolation — many iterations per dispatch via lax.scan so host dispatch latency
is out of the picture — and prints achieved TFLOP/s vs the chip's bf16 peak.
If these hit high MXU efficiency, the train-step gap is elsewhere
(dispatch, BN, bwd, optimizer); if they don't, XLA conv emitters or layout
are the problem.

Usage: python tools/microbench_convs.py [--iters 50] [--batch 128]
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# (name, N-spatial, Cin, Cout, kernel, stride) at batch b, input HxW
CASES = [
    ("stem 7x7/2 3->64 @224", 224, 3, 64, 7, 2),
    ("3x3 64->64 @56", 56, 64, 64, 3, 1),
    ("1x1 64->256 @56", 56, 64, 256, 1, 1),
    ("3x3 128->128 @28", 28, 128, 128, 3, 1),
    ("3x3 256->256 @14", 14, 256, 256, 3, 1),
    ("3x3 512->512 @7", 7, 512, 512, 3, 1),
    ("1x1 2048->1000-ish fc", 0, 2048, 1000, 0, 0),  # dot_general
]


def peak_flops(kind):
    # one shared table: bench.py MFU, this CLI, and the kernel-tier cost
    # model all read mxnet_tpu.perfmodel
    from mxnet_tpu.perfmodel import peak_flops as _pf
    return _pf(kind)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=50)
    ap.add_argument("--batch", type=int, default=128)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax import lax
    import numpy as np

    dev = jax.devices()[0]
    peak = peak_flops(dev.device_kind)
    print("device=%s peak_bf16=%.0f TFLOP/s batch=%d iters/dispatch=%d"
          % (dev.device_kind, peak / 1e12, args.batch, args.iters), flush=True)
    b = args.batch

    for name, hw, cin, cout, k, s in CASES:
        if hw == 0:  # FC case
            x = jnp.zeros((b, cin), jnp.bfloat16)
            w = jnp.zeros((cout, cin), jnp.bfloat16)
            flops = 2.0 * b * cin * cout

            def body(c, _, w=w):
                return jnp.matmul(c, w.T) @ w, None

            def f(x, w=w):
                out, _ = lax.scan(body, x, None, length=args.iters)
                return out
            flops *= 2  # two matmuls per body to keep carry shape
        else:
            x = jnp.zeros((b, cin, hw, hw), jnp.bfloat16)
            w = jnp.zeros((cout, cin, k, k), jnp.bfloat16)
            pad = (k - 1) // 2
            dn = lax.conv_dimension_numbers(x.shape, w.shape,
                                            ("NCHW", "OIHW", "NCHW"))
            out_hw = (hw + 2 * pad - k) // s + 1
            flops = 2.0 * b * cout * cin * k * k * out_hw * out_hw

            def body(c, _, w=w, s=s, pad=pad, dn=dn):
                o = lax.conv_general_dilated(
                    c, w, window_strides=(s, s), padding=[(pad, pad)] * 2,
                    dimension_numbers=dn)
                # fold output back to input shape so scan carries it
                # (mean over trailing dims -> broadcast): keeps the conv
                # un-elidable without host traffic
                return c + jnp.mean(o).astype(c.dtype), None

            def f(x, w=w):
                out, _ = lax.scan(body, x, None, length=args.iters)
                return out

        jf = jax.jit(f)
        r = jf(x)
        jax.block_until_ready(r)
        t0 = time.perf_counter()
        r = jf(x)
        jax.block_until_ready(r)
        dt = time.perf_counter() - t0
        per_iter = dt / args.iters
        tf = flops / per_iter / 1e12
        print("%-28s %9.3f ms/iter %8.1f TFLOP/s  %5.1f%% peak"
              % (name, per_iter * 1e3, tf, 100.0 * tf / (peak / 1e12)),
              flush=True)


if __name__ == "__main__":
    main()
