#!/usr/bin/env python
"""Quickest proof that the system still starts on the chip.

    python chip_smoke.py              # one TPU v5e chip
    python chip_smoke.py --multichip  # the four-chip path only, 4 chips

Drives the main path once through the entry points a user would call, at
the published widths of the models the repo trains and serves, and checks
each answer against a reference: the fused ResNet-50 trainer
(``Module.fit(kvstore="tpu_sync")``, one fused step a program),
the server (``export_compiled`` / ``export_generate`` -> ``serve.Server``)
and every Pallas kernel in the tree, compiled by Mosaic.

One process, no child that touches JAX, no fallback: without a TPU it
exits non-zero at once and prints no result, and any failed check is a
non-zero exit. Sizes are fixed below; the phase functions take smaller
ones only for the CPU rehearsal in tests/test_chip_smoke.py. Times and
bytes printed on the way are smoke observations, not benchmark numbers.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
import argparse
import json
import os
import shutil
import sys
import tempfile
import time
import warnings
from typing import NamedTuple

ROOT = os.path.dirname(os.path.abspath(__file__))

# TPU f32 convolutions and matmuls run, by default, as one bf16 pass: 8
# bits of mantissa per product, ~0.4% per layer, and ResNet-50 stacks 53.
# Measured against the f32 CPU forward as max|diff| / max|reference|; the
# v5e came to 0.0017 (PR 21), so 0.02 leaves room for other weights and
# none for a wrong layer.
BF16_FORWARD_TOL = 0.02
# The same artifact at another batch size, on the same chip: only tiling
# and padding differ between the two programs.
SERVE_VS_MODULE_TOL = 2e-3
# A served token must be the dense reference's argmax, or lose to it by
# no more than this share of the reference's logit range: bf16-pass
# matmuls in two differently tiled programs may split a near-tie (the v5e
# split one, under chunked prefill, at 0.0002: PR 21).
ARGMAX_TIE_TOL = 0.005
# One chip against four in float32, largest loss difference over the steps
# as a share of the first loss. On virtual CPU devices the two agree to
# 1e-5; on the v5e the first loss, before any update, differed by 4e-4
# and the third by 4e-3 (PR 21), which the XLA:TPU programs for 128 and
# for 32 images a chip must account for, since the framework's part is
# the same on both backends (PERF.md, open questions). A reduce that
# summed where it should average would be off by the order of one.
MULTICHIP_F32_LOSS_TOL = 0.02
# Under bf16 compute every activation is rounded again, and SGD with
# momentum on one repeated batch amplifies that from step to step, so the
# bf16 run is held only to its first loss, taken before any update.
MULTICHIP_BF16_FIRST_LOSS_TOL = 0.02


def say(phase, **kv):
    print("[%s] %s" % (phase, " ".join("%s=%s" % i for i in kv.items())),
          flush=True)


def check(ok, what):
    if not ok:
        raise SystemExit("chip_smoke: FAILED: %s" % what)


def _rel_err(got, ref):
    """max|got - ref| / max|ref|; of several arrays (a tuple each), the
    worst: each is held to its own scale."""
    import numpy as np
    if isinstance(ref, (tuple, list)):
        return max(_rel_err(g, r) for g, r in zip(got, ref))
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    check(np.isfinite(got).all(), "non-finite values in a result")
    return float(np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-30))


# --------------------------------------------------------------- 1. device
def describe():
    """Versions, devices and the compile cache in force."""
    import importlib.metadata as md
    import jax
    devs = jax.devices()
    say("device", jax=jax.__version__, jaxlib=md.version("jaxlib"),
        libtpu=md.version("libtpu"), platform=devs[0].platform,
        kind=repr(devs[0].device_kind), count=len(devs))
    say("device",
        compile_cache=jax.config.jax_compilation_cache_dir,
        from_env=bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")))


def phase_device():
    """The native library is built in this run from src/*.cc and loaded;
    ``mx.tpu()`` is the chip."""
    describe()
    import mxnet_tpu as mx
    from mxnet_tpu import runtime
    so = os.path.join(ROOT, "mxnet_tpu", "libmxtpu.so")
    if os.path.exists(so):
        os.remove(so)   # whatever lay in the tree is not what git commits
    t0 = time.time()
    lib = runtime.get_lib()     # runs `make -C src`: no .so is there
    check(lib is not None, "native library: %s" % runtime.load_error())
    check(os.path.getmtime(so) >= t0 - 1.0, "libmxtpu.so was not rebuilt")
    say("device", libmxtpu="built+loaded", seconds="%.1f" % (time.time() - t0))
    check(mx.tpu().jax_device.platform == "tpu", "mx.tpu() is not a TPU")


# ---------------------------------------------------------------- 2. train
def _learnable_batch(batch, side, classes, seed=0):
    """One batch whose labels follow from the pixels: class k carries
    template k under unit noise. Repeated, its loss has to fall."""
    import numpy as np
    rng = np.random.RandomState(seed)
    n_cls = min(8, classes)
    label = (np.arange(batch) % n_cls).astype(np.float32)
    templates = rng.randn(n_cls, 3, side, side).astype(np.float32)
    data = templates[label.astype(int)] + \
        rng.randn(batch, 3, side, side).astype(np.float32)
    return data, label


def _fetch(mod):
    """Host fetch of one parameter element: returns only after every
    dispatched step that feeds it has run."""
    import jax
    import numpy as np
    arr = mod._exec.arg_dict[mod._param_names[0]]._data
    return float(np.asarray(jax.device_get(arr)).ravel()[0])


def _fit_epochs(mod, it, epochs, losses, stamps, bf16=True, lr=0.05):
    """``epochs`` epochs of ``Module.fit``; every epoch ends in a host
    fetch, then its loss and time are kept.
    ``bf16``: bf16 compute over f32 masters (``multi_precision``), the
    configuration the repo benches; else float32 throughout."""
    import mxnet_tpu as mx
    metric = mx.metric.CrossEntropy()

    def epoch_end(epoch, symbol, arg_params, aux_params):
        _fetch(mod)
        losses.append(float(metric.get()[1]))
        stamps.append(time.perf_counter())

    mod.fit(it, num_epoch=epochs, eval_metric=metric,
            kvstore="tpu_sync", optimizer="sgd",
            optimizer_params={"learning_rate": lr, "momentum": 0.9,
                              "multi_precision": bf16},
            initializer=mx.initializer.Xavier(factor_type="in",
                                              magnitude=2.0),
            epoch_end_callback=epoch_end)


def _forward_logits(sym, arg_params, aux_params, x, ctx):
    """One inference forward of the pre-softmax logits on ``ctx``."""
    import mxnet_tpu as mx
    from mxnet_tpu.io import DataBatch
    logits = sym.get_internals()["fc1_output"]
    m = mx.mod.Module(logits, context=ctx, label_names=None)
    m.bind(data_shapes=[("data", x.shape)], for_training=False)
    m.set_params(arg_params, aux_params)
    m.forward(DataBatch(data=[mx.nd.array(x, ctx=ctx)]), is_train=False)
    return m.get_outputs()[0].asnumpy()


def phase_train(ctx=None, num_layers=50, classes=1000, side=224, batch=128,
                steps_per_epoch=4, epochs=5, ref_batch=8, lr=0.05):
    """ResNet-50, batch 128, bf16 compute over f32 masters, through
    ``Module.fit(kvstore="tpu_sync")``: ``epochs`` epochs of
    ``steps_per_epoch`` fused steps, a program each, all on one repeated
    learnable batch."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import models

    ctx = ctx or mx.tpu()
    dev = ctx.jax_device
    sym = models.resnet_symbol(num_classes=classes, num_layers=num_layers,
                               image_shape="3,%d,%d" % (side, side))
    data, label = _learnable_batch(batch, side, classes)
    it = mx.io.NDArrayIter(np.tile(data, (steps_per_epoch, 1, 1, 1)),
                           np.tile(label, steps_per_epoch), batch_size=batch)
    mod = mx.mod.Module(sym, context=ctx)
    mx.random.seed(0)   # the initializer draws from it
    losses, stamps = [], []
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        t0 = time.perf_counter()
        _fit_epochs(mod, it, epochs, losses, stamps, lr=lr)
        check(mod._fused is not None, "the fused step did not engage")
        compiled = mod._fused._jitted_donate._cache_size()
    bad = [str(w.message) for w in caught
           if "donated buffers were not usable" in str(w.message)]
    check(not bad, "donation refused: %s" % bad[:1])
    check(compiled == 1, "fit compiled %d donating step programs, not one"
          % compiled)
    steps = epochs * steps_per_epoch
    check(mod._optimizer.num_update == steps,
          "optimizer saw %d updates, not %d"
          % (mod._optimizer.num_update, steps))
    for name in mod._param_names:
        where = mod._exec.arg_dict[name]._data.devices()
        check(where == {dev}, "%s lives on %s, not %s" % (name, where, dev))
    check(np.isfinite(losses).all(), "non-finite loss: %s" % losses)
    check(losses[-1] < losses[0],
          "loss did not fall on a repeated batch: %s" % losses)

    # epoch 0 holds the compile
    step_s = (stamps[-1] - stamps[0]) / ((epochs - 1) * steps_per_epoch)
    say("train", losses=",".join("%.4f" % v for v in losses))
    say("train", first_epoch_s="%.1f" % (stamps[0] - t0),
        s_per_step="%.4f" % step_s,
        note="smoke_observations_with_h2d_feed_not_a_benchmark")
    stats = dev.memory_stats()
    if stats:
        say("train", peak_bytes_in_use=stats.get("peak_bytes_in_use"))

    # the same parameters, one forward at a small batch: chip vs f32 CPU
    arg_params, aux_params = mod.get_params()
    x = data[:ref_batch]
    on_chip = _forward_logits(sym, arg_params, aux_params, x, ctx)
    on_cpu = _forward_logits(sym, arg_params, aux_params, x, mx.cpu())
    check(on_chip.shape == (ref_batch, classes), "logits shape %s"
          % (on_chip.shape,))
    err = _rel_err(on_chip, on_cpu)
    say("train", logits_vs_f32_cpu="%.5f" % err, tol=BF16_FORWARD_TOL)
    check(err <= BF16_FORWARD_TOL, "logits differ from the f32 CPU forward "
          "by %.4f of their range" % err)
    return {"sym": sym, "arg_params": arg_params, "aux_params": aux_params,
            "data": data, "side": side, "losses": losses}


# ---------------------------------------------------------------- 3. serve
def phase_serve_predict(trained, ctx=None, buckets=(1, 8), rows=(1, 5, 8)):
    """The trained ResNet-50 through ``export_compiled`` -> ``Server``:
    requests of several batch sizes, answers equal to the module's."""
    import mxnet_tpu as mx
    from mxnet_tpu import serving
    from mxnet_tpu.io import DataBatch
    from mxnet_tpu.serve import Server

    ctx = ctx or mx.tpu()
    sym, side = trained["sym"], trained["side"]
    tmp = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        path = os.path.join(tmp, "resnet50.mxtpu")
        t0 = time.perf_counter()
        serving.export_compiled(sym, trained["arg_params"],
                                trained["aux_params"],
                                {"data": (None, 3, side, side)}, path)
        say("serve", predict_artifact_mb=os.path.getsize(path) >> 20,
            export_s="%.1f" % (time.perf_counter() - t0))
        ref = mx.mod.Module(sym, context=ctx)
        ref.bind(data_shapes=[("data", (max(rows), 3, side, side))],
                 for_training=False)
        ref.set_params(trained["arg_params"], trained["aux_params"])
        x = trained["data"][:max(rows)]
        ref.forward(DataBatch(data=[mx.nd.array(x, ctx=ctx)]),
                    is_train=False)
        want = ref.get_outputs()[0].asnumpy()
        srv = Server(path, buckets=tuple(buckets), batch_timeout_ms=2)
        try:
            for n in rows:
                t0 = time.perf_counter()
                got, = srv.predict(x[:n], timeout_ms=600000)
                err = _rel_err(got, want[:n])
                say("serve", predict_rows=n, err="%.2e" % err,
                    seconds="%.2f" % (time.perf_counter() - t0))
                check(got.shape == want[:n].shape, "predict shape %s"
                      % (got.shape,))
                check(err <= SERVE_VS_MODULE_TOL, "served answer differs "
                      "from the module's forward by %.2e" % err)
        finally:
            srv.close()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def gpt2_small_spec(num_layers=12, max_slots=8):
    """GPT-2-small widths: vocab 50257, 768 wide, 12 heads, a 1024-token
    context as 64 pages of 16. Weights are random, from a fixed seed."""
    from mxnet_tpu.serve import decode_model as dm
    return dm.DecoderSpec(vocab=50257, dim=768, num_heads=12,
                          num_layers=num_layers, max_prompt_len=128,
                          page_size=16, max_pages_per_slot=64,
                          max_slots=max_slots,
                          num_pages=max_slots * 64 + 1)


def _check_generation(params, spec, prompt, served, reference):
    """Served greedy tokens equal the dense reference's, or every served
    token is within ARGMAX_TIE_TOL of the reference's argmax given the
    served prefix."""
    import numpy as np
    import jax.numpy as jnp
    from mxnet_tpu.serve import decode_model as dm
    if list(served) == list(reference):
        return 0.0
    p = {k: jnp.asarray(v) for k, v in params.items()}
    buf = np.zeros(spec.max_context, np.int32)
    toks = list(prompt)
    buf[:len(toks)] = toks
    worst = 0.0
    for tok in served:
        n = len(toks)
        logits = np.asarray(dm._dense_logits_at(
            p, jnp.asarray(buf), jnp.asarray(n, jnp.int32),
            H=spec.num_heads, L=spec.num_layers), np.float32)
        gap = float((logits.max() - logits[tok])
                    / max(logits.max() - logits.min(), 1e-30))
        worst = max(worst, gap)
        check(gap <= ARGMAX_TIE_TOL, "served token %d at position %d loses "
              "to the reference argmax by %.4f of the logit range"
              % (tok, n, gap))
        toks.append(int(tok))
        buf[n] = tok
    return worst


def phase_serve_generate(spec=None, prompt_lens=(5, 40, 200), new_tokens=8,
                         kernel_tier="off"):
    """A decoder through ``export_generate`` -> ``Server.generate``:
    several prompts at temperature 0 against ``reference_generate``. A
    prompt longer than ``max_prompt_len`` makes the artifact a chunked
    one, and chunked prefill runs."""
    import numpy as np
    from mxnet_tpu import config, serving
    from mxnet_tpu.serve import Server
    from mxnet_tpu.serve import decode_model as dm

    spec = spec or gpt2_small_spec()
    chunked = max(prompt_lens) > spec.max_prompt_len
    params = dm.init_params(spec, seed=0)
    rng = np.random.RandomState(1)
    prompts = [rng.randint(2, spec.vocab, size=n).tolist()
               for n in prompt_lens]
    tmp = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        path = os.path.join(tmp, "decoder.mxtpu")
        t0 = time.perf_counter()
        # the tier is resolved when the modules are lowered, at export
        with config.override(kernel_tier=kernel_tier):
            serving.export_generate(params, spec, path, chunked=chunked,
                                    bundle_params=False)
        say("serve", decoder="d%d_l%d_v%d" % (spec.dim, spec.num_layers,
                                             spec.vocab),
            kernel_tier=kernel_tier, chunked=chunked,
            artifact_mb=os.path.getsize(path) >> 20,
            export_s="%.1f" % (time.perf_counter() - t0))
        t0 = time.perf_counter()
        srv = Server(path, timeout_ms=0, max_new_tokens=new_tokens)
        say("serve", server_up_s="%.1f" % (time.perf_counter() - t0))
        try:
            for prompt in prompts:
                t0 = time.perf_counter()
                out = srv.generate(prompt, max_new_tokens=new_tokens,
                                   temperature=0.0, seed=0)
                dt = time.perf_counter() - t0
                want = dm.reference_generate(params, spec, prompt,
                                             new_tokens)
                check(len(out["tokens"]) == new_tokens,
                      "generated %d tokens, not %d"
                      % (len(out["tokens"]), new_tokens))
                gap = _check_generation(params, spec, prompt,
                                        out["tokens"], want)
                say("serve", prompt_len=len(prompt),
                    tokens_equal=out["tokens"] == want,
                    worst_tie_gap="%.4f" % gap, seconds="%.2f" % dt)
            diags = srv.session.check_discipline()
            check(diags == [], "decode discipline: %s" % diags)
        finally:
            srv.close()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


# -------------------------------------------------------------- 4. kernels
class KernelCase(NamedTuple):
    """One Pallas kernel at a width its users run. ``fn`` calls it with
    ``interpret=False``; ``args`` are (shape, dtype, fill) triples, see
    :func:`make_args`; ``ref`` is pure JAX over the same arrays; ``tol``
    bounds max|out - ref| / max|ref|, of every array where they return a
    tuple."""
    name: str
    fn: object
    args: tuple
    ref: object
    tol: float


def _paged_reference(q, k_pages, v_pages, bt, pos, heads, page):
    """Gather every slot's pages, then dense masked softmax attention."""
    import jax
    import jax.numpy as jnp
    S, W, C = q.shape
    ctx = bt.shape[1] * page
    rows = (bt[:, :, None] * page + jnp.arange(page)).reshape(S, ctx)
    f32 = jnp.float32
    kh = k_pages[rows].astype(f32).reshape(S, ctx, heads, C // heads)
    vh = v_pages[rows].astype(f32).reshape(S, ctx, heads, C // heads)
    qh = q.astype(f32).reshape(S, W, heads, C // heads)
    s = jnp.einsum("swhd,sthd->shwt", qh, kh) / (C // heads) ** 0.5
    mask = jnp.arange(ctx)[None, None, :] <= \
        (pos[:, None] + jnp.arange(W)[None, :])[:, :, None]
    s = jnp.where(mask[:, None], s, -1e30)
    o = jnp.einsum("shwt,sthd->swhd", jax.nn.softmax(s, axis=-1), vh)
    return o.reshape(S, W, C).astype(q.dtype)


def _bn_act_reference(x, gamma, beta, *residual, eps=1e-3):
    """Training-mode BatchNorm over (N, H, W), residual add, ReLU."""
    import jax.numpy as jnp
    x32 = x.astype(jnp.float32)
    mean = x32.mean(axis=(0, 2, 3), keepdims=True)
    var = ((x32 - mean) ** 2).mean(axis=(0, 2, 3), keepdims=True)
    y = (x32 - mean) / jnp.sqrt(var + eps) * gamma[None, :, None, None] \
        + beta[None, :, None, None]
    if residual:
        y = y + residual[0].astype(jnp.float32)
    return jnp.maximum(y, 0.0).astype(x.dtype)


def _scale_bias_act_reference(x, scale, bias, act):
    import jax
    import jax.numpy as jnp
    y = x.astype(jnp.float32) * scale + bias
    y = jax.nn.gelu(y, approximate=False) if act == "gelu" \
        else jnp.maximum(y, 0.0)
    return y.astype(x.dtype)


def _window_reference(q, k, v, window, block=512, scale=None):
    """The dense form of grouped-query attention under a window, float32,
    for ``block`` rows of queries at a time (all the keys at once would be
    8.6 GB of scores at 8,192 tokens): query head ``i`` reads KV head ``i
    // (H / H_kv)``, key ``j`` visible to query ``i`` when ``i - window <
    j <= i`` (``window`` None: ``j <= i``); the scores times ``scale``
    (None: ``1 / sqrt(d)``)."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    b, h, t, d = q.shape
    kf, vf = (jnp.repeat(x.astype(f32), h // k.shape[1], axis=1)
              for x in (k, v))
    scale = d ** -0.5 if scale is None else scale
    window = t if window is None else window

    @jax.checkpoint
    def rows(a):
        qb, start = a
        s = jnp.einsum("bhqd,bhkd->bhqk", qb, kf) * scale
        i = start + jnp.arange(block)[:, None]
        j = jnp.arange(t)[None, :]
        p = jax.nn.softmax(
            jnp.where((j <= i) & (j > i - window), s, -1e30), axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, vf)

    qb = jnp.moveaxis(q.astype(f32).reshape(b, h, t // block, block, d), 2, 0)
    o = jax.lax.map(rows, (qb, jnp.arange(t // block) * block))
    return jnp.moveaxis(o, 0, 2).reshape(b, h, t, d).astype(q.dtype)


def _kda_tiles(q, k, v, g, beta):
    """Normals as a KDA core's inputs: q and k of unit length (q over
    sqrt(d) besides), a log decay a token from -0.001 to -6, beta within
    (0.05, 0.95)."""
    import jax
    import jax.numpy as jnp

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True))
    cdf = jax.scipy.stats.norm.cdf
    return (unit(q) * q.shape[-1] ** -0.5, unit(k), v,
            -jnp.exp(jnp.log(1e-3) + cdf(g) * jnp.log(6e3)),
            0.05 + 0.9 * cdf(beta))


def _ssd_group_args(x, bm, cm, dt, dt_bias, a_log, d, s):
    """Normals as a group of Mamba-2's core takes them: B and C over
    N^(1/4) (so C.B is of order 1), ``delta`` about 0.02 and ``exp(A_log)``
    from 1 to 16 (a token's decay down to about 0.7, a chunk's to
    exp(-80)), the skip about 1; (consts, state, xs) in
    ``lm_ops._ssd_group``'s order."""
    import jax
    import jax.numpy as jnp
    cdf = jax.scipy.stats.norm.cdf
    scale = bm.shape[-1] ** -0.25
    return ((dt_bias - 4.0, jnp.log(1.0 + 15.0 * cdf(a_log)), 1.0 + 0.1 * d),
            s, (x, (bm * scale).astype(bm.dtype),
                (cm * scale).astype(cm.dtype), dt))


def _kda_scanned(s0, u0, w, m, q_in, k_out, g_end):
    """Normals as what the scan over a KDA group's chunks takes: the
    products' left factors small enough that the state neither dies nor
    grows over the group, a chunk's log decay below -0.05."""
    import jax.numpy as jnp
    c, dk = w.shape[-2:]
    return (s0, u0, w * (0.5 * dk ** -0.5), m * c ** -0.5, q_in * dk ** -0.5,
            k_out * (0.5 * c ** -0.5), -0.05 - jnp.abs(g_end))


def kernel_cases():
    """Every Pallas kernel left in the tree, at real widths. Touches no
    device: tests/test_tpu_aot_compile.py compiles the same table for a
    described chip."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.kernels import attention, bn_act, int8_dequant, mlp, take
    from mxnet_tpu.ops import lm_ops, pallas_flash, pallas_kda
    f32, bf16, i32 = jnp.float32, jnp.bfloat16, jnp.int32
    # bf16 carries 8 bits: 2^-8 per rounding, a few roundings per result.
    # f32 kernels are held to bf16's bound too where they contain matmuls:
    # Mosaic and XLA do not split an f32 product into the same passes.
    tol = {bf16: 2e-2, f32: 2e-2}
    cases = []

    def add(name, fn, args, ref, tol_):
        cases.append(KernelCase(name, fn, tuple(args), ref, tol_))

    # (B, H, T, D): GPT-2-small heads, a 2048-wide model's heads, and
    # those at twice the context
    attn = [(8, 12, 1024, 64, bf16), (4, 16, 1024, 128, f32),
            (4, 16, 2048, 128, bf16)]
    for b, h, t, d, dt in attn[:2]:
        for causal in (False, True):
            qkv = [((b, h, t, d), dt, "normal")] * 3
            add("pallas_flash[%dx%dx%dx%d-%s-causal%d]"
                % (b, h, t, d, dt.__name__, causal),
                lambda q, k, v, c=causal: pallas_flash.flash_attention(
                    q, k, v, 128, 128, c, False),
                qkv,
                lambda q, k, v, c=causal: attention.reference_attention(
                    q, k, v, causal=c), tol[dt])
    # latent attention without positions (Kimi-Linear's MLA): 192-wide
    # q.k beside 128-wide v, 32 heads, at the tile the kernel takes from
    # the shapes (1,024 x 1,024 in bfloat16; float32 operands at these
    # widths fit 512 x 1,024, at 128 / 128 they fill the 16 MiB of VMEM)
    b, h, t = 1, 32, 2048
    for d, dv, dt in [(192, 128, bf16), (192, 128, f32), (128, 128, f32)]:
        add("pallas_flash[%dx%dx%dx%d/%d-%s-causal1]"
            % (b, h, t, d, dv, dt.__name__),
            lambda q, k, v: pallas_flash.flash_attention(
                q, k, v, None, None, True, False),
            [((b, h, t, d), dt, "normal")] * 2 + [((b, h, t, dv), dt,
                                                   "normal")],
            lambda q, k, v: attention.reference_attention(
                q, k, v, causal=True), tol[dt])
    # a window layer of Trinity-Mini: 32 query heads of 128 over 4 KV
    # heads, keys within 2,048 of a query, 8,192 tokens, the kernel's own
    # tile; the output, and the three gradients from its cotangent (the blockwise backward sums
    # dk, dv over a group's 8 query heads), each against the dense form
    b, h, hk, t, d, window = 1, 32, 4, 8192, 128, 2048
    wide, narrow = ((b, h, t, d), bf16, "normal"), ((b, hk, t, d), bf16,
                                                    "normal")

    def windowed(q, k, v):
        return pallas_flash.flash_attention(q, k, v, None, None, True,
                                            False, window)

    def dense(q, k, v):
        return _window_reference(q, k, v, window)
    shape = "%dx%d:%dx%dx%d-bfloat16-window%d" % (b, h, hk, t, d, window)
    add("pallas_flash[%s]" % shape, windowed, [wide, narrow, narrow], dense,
        tol[bf16])
    add("pallas_flash[grad-%s]" % shape,
        lambda q, k, v, do: jax.vjp(windowed, q, k, v)[1](do),
        [wide, narrow, narrow, wide],
        lambda q, k, v, do: jax.vjp(dense, q, k, v)[1](do), tol[bf16])
    # Granite 4.0-H's attention layer: 32 query heads of 64 over 8 KV
    # heads, no window, the scores times attention_multiplier 1/64 (not 1 /
    # sqrt(64)), 8,192 tokens, the kernel's own tile; output and gradients
    hk, d, mult = 8, 64, 0.015625
    wide, narrow = ((b, h, t, d), bf16, "normal"), ((b, hk, t, d), bf16,
                                                    "normal")

    def scaled(q, k, v):
        return pallas_flash.flash_attention(q, k, v, None, None, True, False,
                                            None, mult)

    def dense_scaled(q, k, v):
        return _window_reference(q, k, v, None, scale=mult)
    shape = "%dx%d:%dx%dx%d-bfloat16-scale%g" % (b, h, hk, t, d, mult)
    add("pallas_flash[%s]" % shape, scaled, [wide, narrow, narrow],
        dense_scaled, tol[bf16])
    add("pallas_flash[grad-%s]" % shape,
        lambda q, k, v, do: jax.vjp(scaled, q, k, v)[1](do),
        [wide, narrow, narrow, wide],
        lambda q, k, v, do: jax.vjp(dense_scaled, q, k, v)[1](do), tol[bf16])
    # the backward kernel (``pallas_flash.flash_bwd``) against the plain
    # blockwise backward it replaces, on the same output, rows'
    # log-sum-exp and cotangent (drawn, not made by a forward: the
    # reference runs at the highest precision, which no bfloat16 Pallas
    # product takes): Ouro-2.6B's core (16 heads of 128, each its own KV
    # head) and Kimi-Linear's latent attention (32 heads, 192-wide q.k,
    # 128-wide v), causal, 8,192 tokens, the kernel's own tile
    for h, d, dv in [(16, 128, 128), (32, 192, 128)]:
        wide, narrow = ((1, h, 8192, d), bf16, "normal"), \
            ((1, h, 8192, dv), bf16, "normal")
        add("pallas_flash[bwd-1x%dx8192x%d/%d-bfloat16-causal1]" % (h, d, dv),
            lambda *a: pallas_flash.flash_bwd(*a, True, False),
            [wide, wide, narrow, narrow, ((1, h, 8192), f32, "normal"),
             narrow],
            lambda *a: pallas_flash._flash_bwd(*a, True), tol[bf16])
    for b, h, t, d, dt in attn:
        qkv = [((b, h, t, d), dt, "normal")] * 3
        add("flash_attn[%dx%dx%dx%d-%s]" % (b, h, t, d, dt.__name__),
            lambda q, k, v: attention.flash_attention(
                q, k, v, causal=True, interpret=False),
            qkv,
            lambda q, k, v: attention.reference_attention(
                q, k, v, causal=True), tol[dt])

    # (slots, dim, heads, page, pages, pages/slot): a 2048-wide decoder
    # with 1024-token contexts, and the GPT-2-small geometry served above
    for s, c, h, page, pages, per in [(32, 2048, 16, 16, 4096, 64),
                                      (8, 768, 12, 16, 513, 64)]:
        for dt in (f32, bf16):
            for w in (1, 4):
                store = ((pages * page, c), dt, "normal")
                cfg = attention.default_config_for(
                    attention.PAGED_OP_NAME,
                    attention.paged_shape_key_shapes((s, w, c), h, page,
                                                     (s, per)))
                add("flash_attn_paged[s%d-c%d-%s-w%d]"
                    % (s, c, dt.__name__, w),
                    lambda q, k, v, bt, pos, h=h, page=page, cfg=cfg:
                    attention.paged_attention(
                        q, k, v, bt, pos, heads=h, page_size=page,
                        config=cfg, interpret=False),
                    [((s, w, c), dt, "normal"), store, store,
                     ((s, per), i32, ("pages", pages)),
                     ((s,), i32, ("randint", 0, per * page - w))],
                    lambda q, k, v, bt, pos, h=h, page=page:
                    _paged_reference(q, k, v, bt, pos, h, page), tol[dt])

    # the stem, the widest stage-1 and the last stage-4 BatchNorm of the
    # ResNet-50 step at batch 128
    for shape in [(128, 64, 112, 112), (128, 256, 56, 56),
                  (128, 2048, 7, 7)]:
        for residual in (False, True):
            ch = ((shape[1],), f32, "normal")
            stat = ((shape[1],), f32, "positive")
            x = (shape, bf16, "normal")
            add("bn_act[%s-res%d]" % ("x".join(map(str, shape)), residual),
                lambda data, gamma, beta, mm, mv, *res:
                bn_act.fused_bn_act(data, gamma, beta, mm, mv,
                                    res[0] if res else None,
                                    fix_gamma=False, interpret=False)[0],
                [x, ch, ch, ch, stat] + ([x] if residual else []),
                lambda data, gamma, beta, mm, mv, *res:
                _bn_act_reference(data, gamma, beta, *res), tol[bf16])

    # int32 accumulators of the int8 ResNet-50: two conv sites and the FC
    for shape, per_row in [((128 * 256, 56 * 56), True),
                           ((128 * 2048, 49), True), ((128, 1000), False)]:
        coef = ((shape[0], 1) if per_row else (1, shape[1]), f32, "normal")
        add("int8_dequant[%dx%d]" % shape,
            lambda acc, sc, sh, per_row=per_row:
            int8_dequant.dequant_epilogue(acc, sc, sh, per_row=per_row,
                                          interpret=False),
            [(shape, i32, ("randint", -(1 << 15), 1 << 15)), coef, coef],
            lambda acc, sc, sh: jnp.maximum(
                acc.astype(f32) * sc + sh, 0.0), 1e-6)

    # an LM's embedding table and a recommender's
    for vocab, dim in [(50304, 768), (1048576, 128)]:
        for dt in (f32, bf16):
            add("take_rows[%dx%d-%s]" % (vocab, dim, dt.__name__),
                lambda w, i: take.take_rows(w, i, interpret=False),
                [((vocab, dim), dt, "normal"),
                 ((8192,), i32, ("randint", 0, vocab))],
                lambda w, i: jnp.take(w, i, axis=0), 0.0)

    # the GPT-2-small MLP epilogue: 8 x 1024 tokens, 3072 features
    for act in ("gelu", "relu"):
        for dt in (f32, bf16):
            feat = ((3072,), f32, "normal")
            add("scale_bias_act[%s-%s]" % (act, dt.__name__),
                lambda x, s, b, act=act: mlp.fused_scale_bias_act(
                    x, s, b, act=act, interpret=False),
                [((8192, 3072), dt, "normal"), feat, feat],
                lambda x, s, b, act=act: _scale_bias_act_reference(
                    x, s, b, act),
                2e-2 if dt == bf16 else 1e-5)

    # the work inside the chunks of a KDA core: the six values the scan
    # consumes, and the five gradients from their cotangents. Kimi-Linear's
    # tile (a group of 16 chunks of 64 tokens over 32 heads of 128), then
    # the corners of what the kernels take (``pallas_kda.eligible``): the
    # widest head with the longest chunk, and eight short chunks a tile.
    # float32 with fp32-contract products on both sides: held to 1e-4,
    # some twenty times what the chip read (5e-6)
    def pulled(f, n=5):     # the gradients of the first n arguments
        return lambda *a: jax.vjp(f, *a[:n])[1](tuple(a[n:]))

    for b, t, h, d, chunk, sub in [(1, 1024, 32, 128, 64, 16),
                                   (1, 256, 8, 256, 128, 16),
                                   (1, 128, 32, 256, 16, 16)]:
        wide = ((b, t, h, d), f32, "normal")
        tiles = [wide] * 4 + [((b, t, h), f32, "normal")]
        scanned = [((t // chunk, b, h, r, w), f32, "normal")
                   for r, w in pallas_kda._six(chunk, d, d)]

        def kernels(*x, chunk=chunk, sub=sub):
            return pallas_kda.kda_intra(*_kda_tiles(*x), chunk, sub, False)

        def plain(*x, chunk=chunk, sub=sub):
            return lm_ops._intra_plain(*_kda_tiles(*x), chunk, sub)
        shape = "%dx%dx%dx%d-chunk%d" % (b, t, h, d, chunk)
        add("pallas_kda[fwd-%s]" % shape, kernels, tiles, plain, 1e-4)
        add("pallas_kda[bwd-%s]" % shape, pulled(kernels), tiles + scanned,
            pulled(plain), 1e-4)

    # the scan from chunk to chunk of a KDA group with the state in VMEM:
    # the last state and o, and the seven gradients from their cotangents.
    # Kimi-Linear's group (16 chunks of 64 tokens over 32 heads of 128),
    # then the corners of ``pallas_kda.scan_eligible``: the widest head
    # with the longest chunk, and short chunks (four heads' blocks fit)
    def scan(*x):
        return pallas_kda.kda_scan(*_kda_scanned(*x), False)

    def scan_plain(*x):
        return lm_ops._scan_plain(*_kda_scanned(*x))

    for n, b, h, d, chunk in [(16, 1, 32, 128, 64), (2, 1, 8, 256, 128),
                              (8, 1, 32, 256, 16)]:
        state = ((b, h, d, d), f32, "normal")
        six = [((n, b, h, r, w), f32, "normal")
               for r, w in pallas_kda._six(chunk, d, d)]
        shape = "%dx%dx%dx%d-chunk%d" % (b, n * chunk, h, d, chunk)
        add("pallas_kda[scan-fwd-%s]" % shape, scan, [state] + six,
            scan_plain, 1e-4)
        add("pallas_kda[scan-bwd-%s]" % shape, pulled(scan, 7),
            [state] + six + [state, six[0]], pulled(scan_plain, 7), 1e-4)
    # a group of Mamba-2's core (``lm_ops._ssd_group``) as the two kernels
    # of ``ops/pallas_ssd.py``: the state after the group and y, and the
    # gradients of x, B, C, dt, the three per-head vectors and the state
    # on entry from the cotangents of both. Granite-4.0-H's group (8
    # chunks of 256 tokens, 64 heads of 64, a state of 128, bfloat16
    # operands), then the corners of ``pallas_ssd.eligible``: heads of
    # 128 over a state of 256 in chunks of 128, float32. bfloat16 products
    # on both sides, rounded at the same points: the bf16 bound
    def ssd(h, chunk):
        def kernels(*a):
            from mxnet_tpu.kernels import tier
            with tier.force_compiled():
                return lm_ops._ssd_kernel_group(h, chunk,
                                                *_ssd_group_args(*a))

        def plain(*a):
            return lm_ops._ssd_group(h, chunk, *_ssd_group_args(*a))
        return kernels, plain

    for b, t, h, p, n, chunk, dt in [(1, 2048, 64, 64, 128, 256, bf16),
                                     (1, 512, 4, 128, 256, 128, f32)]:
        kernels, plain = ssd(h, chunk)
        vec = ((h,), f32, "normal")
        given = [((b, t, h * p), dt, "normal"), ((b, t, n), dt, "normal"),
                 ((b, t, n), dt, "normal"), ((b, t, h), f32, "normal"),
                 vec, vec, vec, ((b, h, p, n), f32, "normal")]
        shape = "%dx%dx%dx%d-n%d-chunk%d-%s" % (b, t, h, p, n, chunk,
                                                dt.__name__)
        add("pallas_ssd[fwd-%s]" % shape, kernels, given, plain, tol[dt])
        add("pallas_ssd[bwd-%s]" % shape, pulled(kernels, 8),
            given + [((b, h, p, n), f32, "normal"),
                     ((b, t, h * p), f32, "normal")],
            pulled(plain, 8), tol[dt])
    return cases


def make_args(case, seed=0):
    """Arrays for one case, made from ``seed`` on the default device (a
    hundred million normals take the host seconds, the chip none)."""
    import jax
    import jax.numpy as jnp
    out = []
    for i, (shape, dtype, fill) in enumerate(case.args):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), i)
        if fill == "normal":
            a = jax.random.normal(key, shape, jnp.float32).astype(dtype)
        elif fill == "positive":
            a = jax.random.uniform(key, shape, dtype, 0.5, 1.5)
        elif fill[0] == "randint":
            a = jax.random.randint(key, shape, fill[1], fill[2], dtype)
        elif fill[0] == "pages":
            # distinct live pages for every slot; page 0 is the scratch
            n = shape[0] * shape[1]
            a = (jax.random.permutation(key, fill[1] - 1)[:n] + 1) \
                .reshape(shape).astype(dtype)
        else:
            raise ValueError(fill)
        out.append(a)
    return out


def phase_kernels(cases=None):
    """Each kernel compiled by Mosaic, run, and held to its reference."""
    import jax
    from mxnet_tpu.kernels import tier
    check(tier.resolve_interpret() is False,
          "tier.resolve_interpret() is not False on the chip")
    for case in cases if cases is not None else kernel_cases():
        args = make_args(case)
        t0 = time.perf_counter()
        compiled = jax.jit(case.fn).lower(*args).compile()
        check("tpu_custom_call" in compiled.as_text(),
              "%s: no Mosaic kernel in the compiled program" % case.name)
        got = jax.block_until_ready(compiled(*args))
        with jax.default_matmul_precision("highest"):
            want = jax.block_until_ready(jax.jit(case.ref)(*args))
        err = _rel_err(got, want)
        say("kernels", case=case.name, err="%.2e" % err, tol=case.tol,
            seconds="%.1f" % (time.perf_counter() - t0))
        check([g.shape for g in jax.tree.leaves(got)]
              == [w.shape for w in jax.tree.leaves(want)]
              and err <= case.tol,
              "%s: off its reference by %.3e (tol %g)"
              % (case.name, err, case.tol))


def phase_kernel_tier(spec=None, prompt_lens=(5, 40), **generate_kw):
    """A decode artifact exported with the tier on: the served step holds
    the paged flash-attention kernel, and nothing fell back that should
    not have. Depth is cut to two layers and the prompts fit the prefill
    window (no chunk module): every module carries the 50257-row
    embedding and head as constants, and compiling those is what a
    decoder's server start costs, whatever its depth."""
    from mxnet_tpu.kernels import tier
    tier.reset_stats()
    phase_serve_generate(spec or gpt2_small_spec(num_layers=2),
                         prompt_lens=prompt_lens, kernel_tier="auto",
                         **generate_kw)
    stats = tier.stats()
    say("kernels", dispatch=stats["dispatch"], fallback=stats["fallback"])
    check(stats["dispatch"].get("flash_attn_paged", 0) > 0,
          "flash_attn_paged never dispatched: %s" % stats)
    # prefill is exported with a symbolic batch, which no Pallas grid can
    # take: that site keeps its dense path, by design
    unexpected = [k for k in stats["fallback"]
                  if not (k.startswith("flash_attn:")
                          and "symbolic dimension" in k)]
    check(not unexpected, "unexpected kernel fallbacks: %s" % unexpected)


# ------------------------------------------------------------ 5. multichip
def _module_steps(ctx, sym, data, label, steps, bf16):
    """``steps`` one-step epochs of fit on ``ctx``; (losses, module)."""
    import numpy as np
    import mxnet_tpu as mx
    it = mx.io.NDArrayIter(data, label, batch_size=data.shape[0])
    mod = mx.mod.Module(sym, context=ctx)
    mx.random.seed(0)
    losses, stamps = [], []
    t0 = time.perf_counter()
    # a fifth of the trainer's rate: at 0.05 the first steps on this batch
    # overshoot (7.2, 2.4, 9.9 on the v5e), and a comparison between two
    # placements wants a trajectory that does not amplify their rounding
    _fit_epochs(mod, it, steps, losses, stamps, bf16=bf16, lr=0.01)
    check(mod._fused is not None, "the fused step did not engage")
    check(np.isfinite(losses).all(), "non-finite loss: %s" % losses)
    say("multichip", devices=len(ctx) if isinstance(ctx, list) else 1,
        bf16=bf16, losses=",".join("%.5f" % v for v in losses),
        first_epoch_s="%.1f" % (stamps[0] - t0))
    return losses, mod


def _check_placement(mod, n, batch):
    """Code that has never seen more than one chip may put everything on
    the first: look at where things are."""
    ex = mod._exec
    devices = set(ex._mesh.devices.flat)
    check(len(devices) == n, "mesh holds %d distinct devices" % len(devices))
    for name in ("data", "softmax_label"):
        shards = ex.arg_dict[name]._data.addressable_shards
        check({s.device for s in shards} == devices
              and all(s.data.shape[0] == batch // n for s in shards),
              "%s is not split over the %d devices: %s"
              % (name, n, [(s.device, s.data.shape) for s in shards]))
    for name in mod._param_names:
        arr = ex.arg_dict[name]._data
        check(arr.sharding.device_set == devices,
              "%s is on %s" % (name, arr.sharding.device_set))
    text = mod._fused.lower(ex._arg_vals(), ex._aux_vals(),
                            mod._fused_opt_state,
                            met_state=mod._fused_met_state,
                            donate=True).compile().as_text()
    check("all-reduce" in text, "no all-reduce in the compiled step")
    say("multichip", mesh_devices=len(devices),
        all_reduce_ops=text.count(" all-reduce(")
        + text.count(" all-reduce-start("))


def phase_multichip(n=4, num_layers=50, classes=1000, side=224, batch=128,
                    steps=3, mlp_width=4096):
    """The same ResNet-50 at the same global batch over ``n`` chips
    through ``Module(context=[mx.tpu(i) ...])``, in float32 against one
    chip and in bf16, then ``SPMDTrainStep(ddp_bucketed=True)`` against
    GSPMD's own reduce."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import models

    check(jax.device_count() == n, "%d devices, not %d"
          % (jax.device_count(), n))
    sym = models.resnet_symbol(num_classes=classes, num_layers=num_layers,
                               image_shape="3,%d,%d" % (side, side))
    data, label = _learnable_batch(batch, side, classes)
    chips = [mx.tpu(i) for i in range(n)]

    many, mod = _module_steps(chips, sym, data, label, steps, bf16=False)
    _check_placement(mod, n, batch)
    one, _ = _module_steps(mx.tpu(0), sym, data, label, steps, bf16=False)
    # against the first loss: a loss that has fallen near zero would turn
    # rounding into a large ratio
    worst = max(abs(a - b) for a, b in zip(many, one)) / abs(one[0])
    say("multichip", f32_loss_vs_one_chip="%.2e" % worst,
        tol=MULTICHIP_F32_LOSS_TOL)
    check(worst <= MULTICHIP_F32_LOSS_TOL, "float32 losses on %d chips "
          "differ from one chip's by %.2e" % (n, worst))

    # the configuration the repo benches, on the same mesh
    half, _ = _module_steps(chips, sym, data, label, steps, bf16=True)
    first = abs(half[0] - one[0]) / abs(one[0])
    check(first <= MULTICHIP_BF16_FIRST_LOSS_TOL, "first bf16 loss on %d "
          "chips is %.4f off the float32 one" % (n, first))

    _spmd_ddp(n, batch, mlp_width, classes, steps)


def _spmd_ddp(n, batch, width, classes, steps):
    """Bucketed explicit all-reduce under shard_map against GSPMD's, on a
    BatchNorm-free net: per-shard BN statistics would differ by design."""
    import numpy as np
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import SPMDTrainStep, make_mesh

    net = mx.sym.Variable("data")
    for i in range(3):
        net = mx.sym.FullyConnected(net, num_hidden=width, name="fc%d" % i)
        net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=classes, name="head")
    sym = mx.sym.SoftmaxOutput(net, name="softmax")
    mesh = make_mesh({"dp": n}, devices=jax.devices()[:n])
    arg_shapes, _, _ = sym.infer_shape(data=(batch, width))
    pshapes = {k: tuple(s) for k, s in zip(sym.list_arguments(), arg_shapes)
               if k not in ("data", "softmax_label")}
    rng = np.random.RandomState(3)
    x = rng.randn(batch, width).astype(np.float32)
    y = rng.randint(0, classes, (batch,)).astype(np.float32)

    def run(bucketed):
        st = SPMDTrainStep(sym, mesh, dp_axis="dp", lr=0.05, momentum=0.9,
                           ddp_bucketed=bucketed)
        st.compile(pshapes, {}, {"data": (batch, width)},
                   {"softmax_label": (batch,)})
        params, aux, opt = st.init(pshapes, {}, seed=0)
        dp = NamedSharding(mesh, P("dp"))
        key = jax.random.PRNGKey(0)
        for _ in range(steps):
            params, aux, opt, _ = st(
                params, aux, opt, {"data": jax.device_put(x, dp)},
                {"softmax_label": jax.device_put(y, dp)}, key)
        st.quiesce()
        for k, v in params.items():
            check(len(v.sharding.device_set) == n,
                  "%s is on %s" % (k, v.sharding.device_set))
        return {k: np.asarray(jax.device_get(v))
                for k, v in params.items()}, st.ddp_stats()

    ref, _ = run(False)
    got, stats = run(True)
    worst = max(_rel_err(got[k], ref[k]) for k in ref)
    say("multichip", spmd_buckets=stats["buckets"],
        spmd_comm_bytes=stats["comm_bytes"], vs_gspmd="%.2e" % worst)
    # same sums in another order, f32 masters, bf16-pass matmuls
    check(worst <= 1e-2, "bucketed DDP differs from GSPMD by %.3e" % worst)


# --------------------------------------------------------------------- main
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--multichip", action="store_true",
                    help="run only the four-chip path and what it is "
                         "compared with (needs 4 chips)")
    args = ap.parse_args(argv)
    t0 = time.time()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("chip_smoke: JAX found no TPU (devices: %s); there is no "
              "fallback." % (jax.devices(),), file=sys.stderr)
        return 1
    sys.path.insert(0, ROOT)

    if args.multichip:
        describe()
        phase_multichip()
    else:
        phase_device()
        trained = phase_train()
        phase_serve_predict(trained)
        phase_serve_generate()
        phase_kernels()
        phase_kernel_tier()
    say("done", seconds="%.0f" % (time.time() - t0))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
