"""One attention core's forward, and forward plus backward, timed on the
chip at the shapes the benchmark's language-model cells run.

Each core is ``_contrib_FlashAttention``'s: causal, 8,192 tokens, one
sequence, bfloat16. The variants, where the tree has them:

* ``fwd``: the forward kernel alone;
* ``op``: forward plus backward through ``flash_attention``'s own rule;
* ``plain``: the forward with its log-sum-exp, then ``_flash_bwd``;
* ``kernel/<bq>x<bk>``: the same forward, then ``flash_bwd`` at that tile.

Milliseconds a call: the median of five runs of ten calls each, on the
host's clock after ``block_until_ready`` (a call is milliseconds, so the
dispatch is in the noise). Each variant's gradients are compared with
``plain``'s (the largest difference over the largest value). Run it on the
chip::

    python tools/microbench_attention.py --cells ouro,kimi --out a.json

A tree without ``flash_bwd`` (an older checkout) times ``fwd`` and ``op``.
"""
import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# heads, KV heads, q.k width, v width, window, scale
CELLS = {
    "ouro": (16, 16, 128, 128, None, None),
    "kimi": (32, 32, 192, 128, None, None),
    "trinity_full": (32, 4, 128, 128, None, None),
    "trinity_window": (32, 4, 128, 128, 2048, None),
    "granite": (32, 8, 64, 64, None, 0.015625),
}


def _time(fn, args, reps=5, calls=10):
    import jax
    jax.block_until_ready(fn(*args))       # compile, warm
    runs = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        runs.append((time.perf_counter() - t0) / calls * 1e3)
    return statistics.median(runs), out


def _gap(a, b):
    import jax.numpy as jnp
    top = max(float(jnp.max(jnp.abs(y.astype(jnp.float32)))) for y in b)
    return max(float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                     - y.astype(jnp.float32))))
               for x, y in zip(a, b)) / top


def run_cell(name, tiles, tokens=8192, seed=0):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_flash as pf
    h, hk, d, dv, window, scale = CELLS[name]
    interp = jax.default_backend() != "tpu"     # a rehearsal on the CPU
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    bf16 = jnp.bfloat16
    q = jax.random.normal(keys[0], (1, h, tokens, d), bf16)
    k = jax.random.normal(keys[1], (1, hk, tokens, d), bf16)
    v = jax.random.normal(keys[2], (1, hk, tokens, dv), bf16)
    do = jax.random.normal(keys[3], (1, h, tokens, dv), bf16)
    row = {"cell": name, "shape": [h, hk, tokens, d, dv],
           "window": window, "scale": scale}

    def fwd(q, k, v):
        return pf.flash_attention(q, k, v, None, None, True, interp, window,
                                  scale)

    row["fwd"], _ = _time(jax.jit(fwd), (q, k, v))

    def op(q, k, v, do):
        return jax.vjp(fwd, q, k, v)[1](do)
    row["op"], op_grads = _time(jax.jit(op), (q, k, v, do))
    if not hasattr(pf, "flash_bwd"):
        return row
    row["bwd_eligible"] = pf.bwd_eligible(tokens, tokens, d, dv, h // hk,
                                          window, bf16)
    row["bwd_tile"] = list(pf.bwd_tile_for(tokens, tokens, d, dv, 2,
                                           window))

    def plain(q, k, v, do):
        o, lse = pf._flash_fwd(q, k, v, None, None, True, interp, window,
                               scale, with_lse=True)
        return pf._flash_bwd(q, k, v, o, lse, do, True, window=window,
                             scale=scale)
    row["plain"], want = _time(jax.jit(plain), (q, k, v, do))
    row["op_gap"] = _gap(op_grads, want)
    for bq, bk in tiles:
        def kern(q, k, v, do, bq=bq, bk=bk):
            o, lse = pf._flash_fwd(q, k, v, None, None, True, interp,
                                   window, scale, with_lse=True)
            return pf.flash_bwd(q, k, v, o, lse, do, True, interp, window,
                                scale, bq, bk)
        tag = "kernel/%dx%d" % (bq, bk)
        try:
            row[tag], got = _time(jax.jit(kern), (q, k, v, do))
            row[tag + "_gap"] = _gap(got, want)
        except Exception as e:      # a tile the chip's compiler refuses
            row[tag] = "refused: %s" % str(e).splitlines()[0][:200]
    return row


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cells", default=",".join(CELLS))
    ap.add_argument("--tiles", default="512x512,512x1024,1024x512,1024x1024")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("microbench_attention: no TPU (%s)" % dev.platform)
    tiles = [tuple(int(x) for x in t.split("x"))
             for t in args.tiles.split(",") if t]
    rows = []
    for name in args.cells.split(","):
        rows.append(run_cell(name, tiles))
        print(json.dumps(rows[-1]), flush=True)
    result = {"device": dev.device_kind, "rows": rows}
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
