"""The auto-tuner: enumerate a bounded config space, rank, persist.

Offline only (``tools/autotune.py`` drives it) — the training/serving
hot path consults the resulting cache with a dict lookup and never calls
into this module.

Two ranking backends:

* **on-chip** (a real accelerator is attached): jit + warm up each
  candidate kernel on synthetic operands and take the best-of-k median
  wall time — ground truth, TVM-style.
* **chip-free** (CPU host, or ``--chip-free``): score every candidate
  with the static :mod:`cost_model`. Deterministic — identical rankings
  across runs is an acceptance criterion — and good enough to pick
  sane tiles because only the *order* matters.
"""
from __future__ import annotations

import time

from .. import perfmodel as _perfmodel
from . import cost_model as _cm
from . import space as _space
from .cache import shape_bucket_key

__all__ = ["tune", "TuneResult"]


class TuneResult(dict):
    """dict with the fields: op, key, dtype, shapes, source, ranking
    (best first: {config, score_us, features}), best."""


def _runner(op, shapes, dtype, config):
    """Build a jitted synthetic-operand callable for one config (chip
    measurement path; compiled Mosaic, never interpret)."""
    import jax
    import jax.numpy as jnp
    from .. import kernels
    mod = kernels.kernel_module(op)
    jdt = jnp.dtype(dtype)
    if op == "bn_act":
        (R, S), = shapes[:1]
        x = jnp.zeros((R, S), jdt)
        sc = jnp.ones((R, 1), jnp.float32)
        sh = jnp.zeros((R, 1), jnp.float32)
        fn = jax.jit(lambda a: mod._epilogue(
            a, sc, sh, None, "relu", config["block_r"],
            config["block_s"], False))
        args = (x,)
    elif op == "scale_bias_act":
        (R, F), = shapes[:1]
        x = jnp.zeros((R, F), jdt)
        sc = jnp.ones((1, F), jnp.float32)
        b = jnp.zeros((1, F), jnp.float32)
        fn = jax.jit(lambda a: mod._call(
            a, sc, b, "gelu", config["block_r"], config["block_f"],
            False))
        args = (x,)
    elif op == "take_rows":
        (V, D) = shapes[0]
        (L,) = shapes[1]
        w = jnp.zeros((V, D), jdt)
        idx = jnp.arange(L, dtype=jnp.int32) % max(V, 1)
        fn = jax.jit(lambda a, i: mod._call(a, i, config["block_d"],
                                            False))
        args = (w, idx)
    elif op == "flash_attn":
        (BH, Tq, D) = shapes[0]
        Tk = shapes[1][1]
        q = jnp.zeros((BH, 1, Tq, D), jdt)
        kv = jnp.zeros((BH, 1, Tk, D), jdt)
        cfg = mod._Cfg(config["block_q"], config["block_k"],
                       Tq == Tk, False)       # causal when self-attention
        fn = jax.jit(lambda a, b_, c: mod._call(a, b_, c, cfg))
        args = (q, kv, kv)
    elif op == "flash_attn_paged":
        (S, W, H, Dh) = shapes[0]
        (MP, page) = shapes[1]
        n_pages = S * MP + 1                  # page 0 = scratch, like serve
        kv = jnp.zeros((n_pages * page, H * Dh), jdt)
        q = jnp.zeros((S, W, H * Dh), jdt)
        bt = (1 + jnp.arange(S * MP, dtype=jnp.int32)).reshape(S, MP)
        pos = jnp.full((S,), MP * page - 1, jnp.int32)   # worst-case ctx
        fn = jax.jit(lambda a, kp, vp, b_, p_: mod._paged_call(
            a, kp, vp, b_, p_, heads=H, page_size=page,
            block_h=config["block_h"], interpret=False))
        args = (q, kv, kv, bt, pos)
    else:
        raise KeyError("no tuner runner for op %r" % (op,))
    return fn, args


def _measure_us(fn, args, iters=20, repeats=3):
    out = fn(*args)
    jax_block(out)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax_block(out)
        best = min(best, (time.perf_counter() - t0) * 1e6 / iters)
    return best


def jax_block(out):
    import jax
    for leaf in jax.tree_util.tree_leaves(out):
        if hasattr(leaf, "block_until_ready"):
            leaf.block_until_ready()


def _config_key(config):
    return ",".join("%s=%s" % (k, config[k]) for k in sorted(config))


def tune(op, shapes, dtype, chip_free=None, model=None,
         device_kind=None, iters=20):
    """Rank every candidate config for (op, shapes, dtype).

    ``shapes`` is the kernel's canonical shape tuple-of-tuples (what
    ``<kernel>.shape_key_shapes`` returns). Returns a :class:`TuneResult`
    whose ``ranking`` is best-first and fully deterministic in chip-free
    mode (ties broken by config key).
    """
    import jax
    if chip_free is None:
        chip_free = jax.default_backend() == "cpu"
    if device_kind is None:
        device_kind = _perfmodel.modelled_device_kind()
    model = model or _cm.default_model()
    shapes = tuple(tuple(s) for s in shapes)
    candidates = _space.space_for(op, shapes, str(dtype))
    rows = []
    for config in candidates:
        feat = _cm.features(op, shapes, str(dtype), config, device_kind)
        if chip_free:
            score = model.predict(feat)
            source = "model"
        else:
            fn, args = _runner(op, shapes, dtype, config)
            score = _measure_us(fn, args, iters=iters)
            source = "measured"
        rows.append({"config": config, "score_us": float(score),
                     "features": feat, "source": source})
    rows.sort(key=lambda r: (r["score_us"], _config_key(r["config"])))
    if not chip_free:
        # feed the chip-free cost model: measured (features, time) pairs
        # land in the timing log for `autotune.py --recalibrate`
        from . import timings as _timings
        try:
            _timings.record_rows(op, shapes, str(dtype), device_kind, rows)
        except OSError:
            pass
    key = shape_bucket_key(op, shapes, str(dtype))
    return TuneResult(
        op=op, key=key, dtype=str(dtype),
        shapes=[list(s) for s in shapes],
        source=("model" if chip_free else "measured"),
        device_kind=device_kind,
        ranking=rows, best=rows[0])
