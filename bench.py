"""Benchmark: ResNet-50 synthetic-data training throughput on one chip,
measured THROUGH the product API (`Module.fit`), not around it.

Mirrors the reference's `train_imagenet.py --benchmark 1` measurement
(reference docs/faq/perf.md:228-237; BASELINE.md). vs_baseline compares
against the reference's published V100 number at the same batch size:
363.69 img/s (batch 128, MXNet 1.2 + cuDNN, docs/faq/perf.md:237).

Methodology:
* `Module.fit(kvstore='tpu_sync', optimizer_params={'multi_precision':
  True})` — the fused one-XLA-program step (module/fused.py): fwd+bwd+
  optimizer update, f32 master weights, bf16 compute (the TPU analog of the
  reference's fp16 multi-precision path, docs/faq/perf.md:181-194);
* one device-resident synthetic batch repeated (the reference's
  --benchmark 1 semantics), `eval_metric=None` so no per-batch host sync;
* timing ends on a FORCED HOST FETCH of updated params (device_get), which
  cannot return before the whole dependency chain ran; a fully-synchronous
  per-step cross-check is also reported;
* MFU = achieved FLOP/s / chip peak, FLOPs from XLA's cost analysis of the
  compiled fused step (fallback: analytic 3 x 2 x 4.1 GFLOP/img).

One JSON line on stdout: {"metric", "value", "unit", "vs_baseline", ...}.
Without a TPU the run exits non-zero and prints no result, unless the CPU
was asked for by name (BENCH_PLATFORM=cpu: a smoke run of the code paths,
its numbers are not device numbers). A leg that raises leaves its message
in its field and makes the exit code non-zero.
"""
import json
import os
import subprocess
import sys
import time
import traceback

BASELINE_IMG_S = 363.69  # V100 ResNet-50 train, batch 128 (perf.md:237)


_FAILED = []  # legs that raised; main() exits non-zero when any did


def _failed(leg, e):
    """Field value for a leg that raised: the run goes on, so that the
    other legs still report, and exits non-zero at the end."""
    _FAILED.append(leg)
    traceback.print_exc(file=sys.stderr)
    return "failed: %s" % e


def _secondary_legs(out, on_tpu):
    """The two other BASELINE.json metrics (kvstore push/pull µs, Gluon
    LSTM tokens/sec) plus the 2-process dist kv leg. None need the chip,
    so they are measured fresh even on CPU-only rounds."""
    try:
        from tools.bandwidth import measure as _kv_us
        out["kvstore_push_pull_us"] = _kv_us(
            "local", size_mb=1.0, reps=10 if on_tpu else 3)["value"]
    except Exception as e:
        out["kvstore_push_pull_us"] = _failed("kvstore_push_pull_us", e)
    try:
        from tools.bench_lstm import measure as _lstm
        out["lstm_tokens_per_sec"] = _lstm(
            steps=10 if on_tpu else 2)["value"]
    except Exception as e:
        out["lstm_tokens_per_sec"] = _failed("lstm_tokens_per_sec", e)
    # dist leg: 2-process launch group on the host CPUs, so the µs
    # includes real cross-process serialization + TCP (the reference
    # measures tools/bandwidth/measure.py under a dmlc launch group)
    try:
        out["kvstore_dist_push_pull_us"] = _dist_kv_us()
    except Exception as e:
        out["kvstore_dist_push_pull_us"] = _failed("kvstore_dist_push_pull_us", e)
    # online-serving leg: dynamic-batch ResNet-50 artifact driven by the
    # closed-loop loadgen through mxnet_tpu.serve (BENCH_SERVING=0 skips)
    if os.environ.get("BENCH_SERVING", "1") == "1":
        try:
            out["serving"] = _serving_leg(on_tpu)
        except Exception as e:
            out["serving"] = _failed("serving", e)
    # continuous-batching decode leg: tokens/s goodput, TTFT/TPOT, and
    # the continuous-vs-static speedup on a ragged synthetic workload
    # (BENCH_DECODE=0 skips)
    if os.environ.get("BENCH_DECODE", "1") == "1":
        try:
            out["decode"] = _decode_leg(on_tpu)
        except Exception as e:
            out["decode"] = _failed("decode", e)
    # recommender leg: two-tower step time over the hot-row cache, the
    # sparse-vs-densified DDP comm ratio, and /v1/recommend goodput on
    # Zipf traffic (BENCH_RECO=0 skips)
    if os.environ.get("BENCH_RECO", "1") == "1":
        try:
            out["recommend"] = _reco_leg(on_tpu)
        except Exception as e:
            out["recommend"] = _failed("recommend", e)
    # flash-attention kernel leg: chip-free tile pick + TPU-export custom
    # call census every round, wall microbench only on the chip
    # (BENCH_ATTN=0 skips)
    if os.environ.get("BENCH_ATTN", "1") == "1":
        try:
            out["attention"] = _attention_leg(on_tpu)
            kt = out.get("kernel_tier")
            if isinstance(kt, dict) and isinstance(out["attention"], dict):
                kt["flash_attn_custom_calls"] = \
                    out["attention"].get("census")
        except Exception as e:
            out["attention"] = _failed("attention", e)


def _reco_leg(on_tpu):
    """The PR-15 embedding subsystem end to end: train a pure-embedding
    two-tower model through the hot-row cache + spill store, report the
    per-step time and cache counters, the STATIC sparse-vs-densified
    gradient-exchange ratio (parallel/ddp.py sparse bucket kind — the
    >=10x headline), then export the towers as a format_version-6
    artifact and drive ``/v1/recommend`` with the Zipf closed loop.
    Runs the MXL511 chip-free gate over the served lookup."""
    import tempfile
    from functools import partial as _partial
    import numpy as np
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.embed import HotRowCache, SpillStore
    from mxnet_tpu.embed.serve import export_recommend
    from mxnet_tpu.parallel.ddp import SparseBucket
    from mxnet_tpu.serve import Server
    from tools.serve_loadgen import measure_recommend

    if on_tpu:
        U, I, D, B, steps, cap = 65536, 4096, 64, 512, 30, 8192
    else:
        U, I, D, B, steps, cap = 2048, 1024, 16, 128, 12, 384
    rng = np.random.RandomState(0)
    u_ids = ((rng.zipf(1.3, size=(steps, B)) - 1) % U).astype("int64")
    i_ids = rng.randint(0, I, size=(steps, B)).astype("int64")
    ratings = rng.randn(steps, B).astype("f4")
    lr = np.float32(0.1)

    store_u = SpillStore(U, D, seed=1)
    store_i = SpillStore(I, D, seed=2)
    cache_u = HotRowCache(store_u, cap)
    cache_i = HotRowCache(store_i, min(cap, I))

    @_partial(jax.jit, donate_argnums=(0, 1))
    def step(u_buf, i_buf, us, isl, r):
        uv, iv = u_buf[us], i_buf[isl]
        err = (uv * iv).sum(-1) - r
        d = (2.0 / r.shape[0]) * err
        gu = jnp.zeros_like(u_buf).at[us].add(d[:, None] * iv)
        gi = jnp.zeros_like(i_buf).at[isl].add(d[:, None] * uv)
        return u_buf - lr * gu, i_buf - lr * gi, (err ** 2).sum()

    # warm (compile + first fills), then time
    us, isl = cache_u.ensure(u_ids[0]), cache_i.ensure(i_ids[0])
    cache_u.buf, cache_i.buf, L = step(cache_u.buf, cache_i.buf, us,
                                       isl, jnp.asarray(ratings[0]))
    jax.block_until_ready(L)
    t0 = time.perf_counter()
    for s in range(1, steps):
        us, isl = cache_u.ensure(u_ids[s]), cache_i.ensure(i_ids[s])
        cache_u.buf, cache_i.buf, L = step(
            cache_u.buf, cache_i.buf, us, isl, jnp.asarray(ratings[s]))
        cache_u.note_updated(u_ids[s])
        cache_i.note_updated(i_ids[s])
    jax.block_until_ready(L)
    step_ms = (time.perf_counter() - t0) * 1e3 / (steps - 1)

    # static sparse-DDP exchange plan at a 4-rank mesh: what one step
    # moves coalesced (touched rows) vs densified (the whole table)
    ranks = 4
    plan = [SparseBucket("user", B // ranks, D, U),
            SparseBucket("item", B // ranks, D, I)]
    sparse_b = sum(sb.comm_bytes(ranks) for sb in plan)
    dense_b = sum(sb.densified_bytes() for sb in plan)

    cache_u.flush()
    cache_i.flush()
    art = tempfile.mktemp(suffix=".reco.mxtpu")
    export_recommend(store_u.peek(np.arange(U)),
                     store_i.peek(np.arange(I)), art,
                     max_ids=64, k=10)
    try:
        srv = Server(art, queue_depth=64)
        load = measure_recommend(
            srv, concurrency=8 if on_tpu else 4,
            requests=256 if on_tpu else 64, mean_ids=8, zipf=1.3)
        diags = srv.engine.check_discipline()
        srv.close(drain=True)
    finally:
        try:
            os.unlink(art)
        except OSError:
            pass
    return {
        "platform": "tpu" if on_tpu else "cpu_smoke",
        "table": "%dx%d + %dx%d" % (U, D, I, D),
        "cache_rows": cap,
        "train_step_ms": round(step_ms, 3),
        "train_cache": {k: cache_u.stats()[k] for k in
                        ("hit_rate", "evictions", "spill_bytes",
                         "upload_bytes")},
        "sparse_comm_bytes": sparse_b,
        "densified_comm_bytes": dense_b,
        "sparse_compression": round(dense_b / float(sparse_b), 1),
        "recommend_goodput_qps": load["goodput_qps"],
        "recommend_p50_ms": load["latency_ms"]["p50"],
        "recommend_p99_ms": load["latency_ms"]["p99"],
        "serve_cache_hit_rate": load.get("cache_hit_rate"),
        "mxl511": "clean" if not diags else [str(d) for d in diags],
    }


def _attention_leg(on_tpu):
    """Flash-attention kernel family microbench (kernels/attention.py).

    Chip-free on every round: the tuner's cost model picks the tile
    config for the benched shapes, and a TPU-platform ``jax.export``
    under ``tier.force_compiled()`` proves the custom calls survive
    into the cross-compiled program (``mxk_flash_attn`` /
    ``mxk_flash_attn_paged`` census — the numbers
    tests/test_attention_kernel.py pins). Wall timing of kernel vs the
    dense reference runs only on the chip: the CPU interpreter's wall
    time says nothing about Mosaic."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax import export as _export
    from mxnet_tpu import hlo_stats
    from mxnet_tpu.kernels import attention as _attn
    from mxnet_tpu.kernels import tier as _tier
    from mxnet_tpu.tune import tuner as _tuner

    if on_tpu:
        B, H, T, D = 4, 8, 1024, 64
        S, W, MP, page = 8, 4, 8, 16
    else:
        B, H, T, D = 1, 2, 128, 16
        S, W, MP, page = 2, 2, 2, 8
    leg = {"platform": "tpu" if on_tpu else "cpu_smoke",
           "train_shape": [B, H, T, D],
           "paged_geometry": {"slots": S, "window": W, "pages_per_slot": MP,
                              "page_size": page}}

    # chip-free tile pick for the benched shapes (docs/tuning.md): same
    # ranking tools/autotune.py --chip-free would commit
    shapes = _attn.shape_key_shapes((B, H, T, D), (B, H, T, D))
    res = _tuner.tune("flash_attn", shapes, "float32", chip_free=True)
    leg["config"] = dict(res["best"]["config"])
    leg["model_score_us"] = round(res["best"]["score_us"], 2)
    pshapes = _attn.paged_shape_key_shapes((S, W, H * D), H, page, (S, MP))
    pres = _tuner.tune("flash_attn_paged", pshapes, "float32",
                       chip_free=True)
    leg["paged_config"] = dict(pres["best"]["config"])
    leg["paged_model_score_us"] = round(pres["best"]["score_us"], 2)

    # TPU-platform export census under force_compiled: the kernels must
    # reach the lowered program even when exported from a chip-free host
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, H, T, D).astype("f4"))
    census = {}
    with _tier.force_compiled():
        exp = _export.export(
            jax.jit(lambda a, b_, c: _attn.flash_attention(
                a, b_, c, causal=True)), platforms=["tpu"])(q, q, q)
        for name, n in hlo_stats.pallas_kernel_names(
                exp.mlir_module()).items():
            census[name] = census.get(name, 0) + n
        kv = jnp.zeros(((S * MP + 1) * page, H * D), jnp.float32)
        pq = jnp.asarray(rng.randn(S, W, H * D).astype("f4"))
        bt = jnp.asarray(
            (1 + np.arange(S * MP, dtype=np.int32)).reshape(S, MP))
        pos = jnp.full((S,), page * MP - W, jnp.int32)
        pexp = _export.export(
            jax.jit(lambda a, kp, vp, b_, p_: _attn.paged_attention(
                a, kp, vp, b_, p_, heads=H, page_size=page)),
            platforms=["tpu"])(pq, kv, kv, bt, pos)
        for name, n in hlo_stats.pallas_kernel_names(
                pexp.mlir_module()).items():
            census[name] = census.get(name, 0) + n
    leg["census"] = census

    if on_tpu:
        def _time_us(fn, *args, iters=10):
            out = jax.block_until_ready(fn(*args))
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                for _ in range(iters):
                    out = fn(*args)
                jax.block_until_ready(out)
                best = min(best, (time.perf_counter() - t0) * 1e6 / iters)
            return best
        kern = jax.jit(lambda a, b_, c: _attn.flash_attention(
            a, b_, c, causal=True, config=leg["config"]))
        ref = jax.jit(lambda a, b_, c: _attn.reference_attention(
            a, b_, c, causal=True))
        leg["kernel_us"] = round(_time_us(kern, q, q, q), 1)
        leg["reference_us"] = round(_time_us(ref, q, q, q), 1)
        leg["speedup"] = round(leg["reference_us"]
                               / max(leg["kernel_us"], 1e-9), 2)
    return leg


def _decode_leg(on_tpu):
    """Autoregressive decode through the continuous-batching engine
    (serve/decode.py): export ONE generate artifact, then run the same
    ragged workload — per group of ``max_slots`` requests, all but one
    want a handful of tokens and one wants a long completion — in
    continuous mode (finished slots refill between decode steps) and in
    static mode (a group runs to its last straggler). The headline is
    the goodput ratio; decode STEP counts are reported too since they
    are the deterministic, load-independent form of the same ratio.
    A second pass runs the same workload through a speculative
    (int8-draft, format_version-5) artifact with speculation on vs off
    at matched distribution, reporting accepted-tokens/step, draft
    acceptance rate, and the tokens/s/user + step-count speedups.
    Runs the MXL508 and MXL510 chip-free gates over the served steps."""
    import tempfile
    import numpy as np
    from mxnet_tpu import serving
    from mxnet_tpu.serve import GenerateSession
    from mxnet_tpu.serve import decode_model as _dm

    if on_tpu:
        spec = _dm.DecoderSpec(vocab=512, dim=256, num_heads=8,
                               num_layers=4, max_prompt_len=16,
                               page_size=16, max_pages_per_slot=8,
                               max_slots=16, num_pages=160)
        short_new, long_new, groups = 4, 108, 3
    else:
        spec = _dm.DecoderSpec(vocab=128, dim=64, num_heads=4,
                               num_layers=2, max_prompt_len=8,
                               page_size=8, max_pages_per_slot=6,
                               max_slots=8, num_pages=64)
        short_new, long_new, groups = 2, 40, 3
    params = _dm.init_params(spec, seed=0)
    art = tempfile.mktemp(suffix=".gen.mxtpu")
    t0 = time.perf_counter()
    serving.export_generate(params, spec, art)
    leg = {"platform": "tpu" if on_tpu else "cpu_smoke",
           "model": "gpt_d%d_l%d" % (spec.dim, spec.num_layers),
           "export_s": round(time.perf_counter() - t0, 2),
           "artifact_mb": round(os.path.getsize(art) / 1e6, 1),
           "slots": spec.max_slots, "page_size": spec.page_size,
           "kv_pages": spec.num_pages - 1}

    rng = np.random.RandomState(0)
    S = spec.max_slots
    work = []   # (prompt, max_new)
    for _ in range(groups):
        for j in range(S):
            plen = int(rng.randint(2, spec.max_prompt_len + 1))
            prompt = rng.randint(2, spec.vocab, size=plen).tolist()
            work.append((prompt, long_new if j == S - 1 else short_new))

    def run_mode(continuous, path=art, **skw):
        sess = GenerateSession(path, auto_start=False,
                               continuous=continuous, timeout_ms=0,
                               queue_depth=len(work) + 1, **skw)
        t1 = time.perf_counter()
        reqs = [sess.submit(p, max_new_tokens=n, temperature=0.0, seed=0)
                for p, n in work]
        rounds = 0
        cap = sum(n for _, n in work) * 4 + 64
        while not all(r.done() for r in reqs) and rounds < cap:
            sess.run_round()
            rounds += 1
        wall = time.perf_counter() - t1
        outs = [r.result(timeout=1.0) for r in reqs]
        toks = sum(len(o["tokens"]) for o in outs)
        ttfts = sorted(o["ttft_ms"] for o in outs)
        tpots = sorted(o["tpot_ms"] for o in outs
                       if o["tpot_ms"] is not None)
        sess._publish_window(force=True)
        snap = sess.metrics_.snapshot()
        steps = snap["decode_steps"]
        diags = (sess.check_discipline()
                 + sess.check_speculative_discipline()) \
            if continuous else []
        mxl512 = None
        if continuous:
            from mxnet_tpu.kernels import tier as _ktier
            if _ktier.tier() != "off":
                a = sess.check_attention_discipline()
                mxl512 = "clean" if not a else [str(d) for d in a]
        sess.close(drain=True)

        def pct(xs, q):
            return round(xs[min(len(xs) - 1,
                                int(q / 100.0 * len(xs)))], 3) \
                if xs else None
        res = {"tokens": toks, "wall_s": round(wall, 3),
               "tokens_per_s": round(toks / wall, 1),
               "decode_steps": steps,
               "ttft_ms_p50": pct(ttfts, 50),
               "ttft_ms_p99": pct(ttfts, 99),
               "tpot_ms_p50": pct(tpots, 50),
               "tpot_ms_p99": pct(tpots, 99)}
        sp = snap.get("speculative")
        if sp and sp.get("steps"):
            res["accepted_tokens_per_step"] = sp["accepted_tokens_per_step"]
            res["draft_acceptance_rate"] = sp["draft_acceptance_rate"]
        if mxl512 is not None:
            res["mxl512"] = mxl512
        return res, diags, [o["tokens"] for o in outs]

    # speculative leg: the SAME workload through a format_version-5
    # artifact bundling the int8 draft, speculation on vs off. Greedy
    # decode makes the comparison matched-distribution by construction
    # (the token streams are asserted identical); the step ratio is the
    # deterministic, load-independent form of the tokens/s/user speedup.
    draft = _dm.quantize_decoder_params(params)
    art5 = tempfile.mktemp(suffix=".spec.mxtpu")
    t0 = time.perf_counter()
    # k=4 rather than the roofline suggestion: these bench models are
    # far below the memory-bound regime the roofline models, and the
    # headline (step-count ratio at matched distribution) needs a
    # window deep enough for the acceptance tail to show
    serving.export_generate(params, spec, art5, draft_params=draft,
                            speculate_k=4)
    export5_s = round(time.perf_counter() - t0, 2)

    try:
        cont, diags, _ = run_mode(True)
        stat, _, _ = run_mode(False)
        # kernel on/off re-emit: the SAME continuous workload with the
        # Pallas attention tier forced auto vs off. The tier is resolved
        # when the decode module is LOWERED, so each arm exports its own
        # artifact under the override. Greedy decode pins the token
        # streams bitwise-equal (the kernel parity bar); the wall ratio
        # on a CPU round is the chip-free (interpreter) form of the
        # number — only the on-chip ratio is a performance claim.
        from mxnet_tpu.config import flags as _flags
        prev_tier = _flags.kernel_tier
        arts = {"auto": tempfile.mktemp(suffix=".kon.mxtpu"),
                "off": tempfile.mktemp(suffix=".koff.mxtpu")}
        try:
            _flags.set("kernel_tier", "auto")
            serving.export_generate(params, spec, arts["auto"])
            kern_on, _, ktoks_on = run_mode(True, path=arts["auto"])
            _flags.set("kernel_tier", "off")
            serving.export_generate(params, spec, arts["off"])
            kern_off, _, ktoks_off = run_mode(True, path=arts["off"])
        finally:
            _flags.set("kernel_tier", prev_tier)
            for f in arts.values():
                try:
                    os.unlink(f)
                except OSError:
                    pass
        spec_on, diags510, toks_on = run_mode(True, path=art5,
                                              speculative=True)
        spec_off, _, toks_off = run_mode(True, path=art5,
                                         speculative=False)
    finally:
        for f in (art, art5):
            try:
                os.unlink(f)
            except OSError:
                pass
    leg["continuous"] = cont
    leg["static"] = stat
    leg["speedup_tokens_per_s"] = round(
        cont["tokens_per_s"] / stat["tokens_per_s"], 2) \
        if stat["tokens_per_s"] else None
    leg["speedup_steps"] = round(
        stat["decode_steps"] / float(cont["decode_steps"]), 2) \
        if cont["decode_steps"] else None
    leg["mxl508"] = "clean" if not diags else [str(d) for d in diags]
    leg["kernel_on"] = kern_on
    leg["kernel_off"] = kern_off
    leg["kernel_tokens_matched"] = ktoks_on == ktoks_off
    leg["kernel_wall_ratio"] = round(
        kern_off["wall_s"] / kern_on["wall_s"], 2) \
        if kern_on["wall_s"] else None
    # the perfmodel policy's chosen depth next to the measured
    # acceptance, so the suggest_speculation_depth heuristic is
    # auditable against what the chip actually accepted
    spec_on["policy_k"] = _dm.suggest_speculation_depth(spec)
    spec_on["export_s"] = export5_s
    leg["speculative"] = spec_on
    leg["speculative_baseline"] = spec_off
    leg["speculative_matched"] = toks_on == toks_off
    leg["speculative_speedup_tokens_per_s_user"] = round(
        spec_on["tokens_per_s"] / spec_off["tokens_per_s"], 2) \
        if spec_off["tokens_per_s"] else None
    leg["speculative_speedup_steps"] = round(
        spec_off["decode_steps"] / float(spec_on["decode_steps"]), 2) \
        if spec_on["decode_steps"] else None
    leg["mxl510"] = "clean" if not diags510 else [str(d) for d in diags510]
    return leg


def _serving_leg(on_tpu):
    """ResNet-50 through the online serving runtime: export ONE
    dynamic-batch artifact, then for each batch bucket run a dedicated
    single-bucket server under the closed-loop load generator
    (tools/serve_loadgen.py, concurrency = bucket) and report p50/p99
    latency, goodput and padding-waste. Buckets {1, 8, 32} on the chip;
    a shrunken smoke (64x64 input, buckets {1, 8}) on CPU rounds so the
    serving path itself is regression-tracked every round."""
    import tempfile
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import models
    from mxnet_tpu.serve import Server
    from tools.serve_loadgen import measure

    side = 224 if on_tpu else 64
    classes = 1000 if on_tpu else 10
    buckets = (1, 8, 32) if on_tpu else (1, 8)
    reqs_per_bucket = 8 if on_tpu else 4

    sym = models.resnet_symbol(num_classes=classes, num_layers=50,
                               image_shape="3,%d,%d" % (side, side))
    shapes, _, aux_shapes = sym.infer_shape(data=(2, 3, side, side))
    rng = np.random.RandomState(0)
    args = {n: mx.nd.array(rng.uniform(-0.05, 0.05, s).astype("f4"))
            for n, s in zip(sym.list_arguments(), shapes)
            if n not in ("data", "softmax_label")}
    aux = {n: mx.nd.array(np.ones(s, "f4") if "var" in n
                          else np.zeros(s, "f4"))
           for n, s in zip(sym.list_auxiliary_states(), aux_shapes)}
    art = tempfile.mktemp(suffix=".mxtpu")
    t0 = time.perf_counter()
    mx.serving.export_compiled(sym, args, aux,
                               {"data": (None, 3, side, side)}, art)
    leg = {"platform": "tpu" if on_tpu else "cpu_smoke",
           "model": "resnet50_%dx%d" % (side, side),
           "export_s": round(time.perf_counter() - t0, 2),
           "artifact_mb": round(os.path.getsize(art) / 1e6, 1),
           "buckets": {}}
    try:
        for b in buckets:
            srv = Server(art, buckets=(b,), batch_timeout_ms=2)
            t1 = time.perf_counter()
            # pre-build the bucket engine: compile+warmup must not
            # pollute the latency percentiles (one-time cost, reported
            # separately)
            srv.model.engine_cache.engine(b)
            compile_s = time.perf_counter() - t1
            res = measure(srv, concurrency=b,
                          requests=reqs_per_bucket * b,
                          timeout_ms=600000)
            snap = srv.metrics()["buckets"].get(str(b), {})
            srv.close(drain=True)
            leg["buckets"][str(b)] = {
                "p50_ms": round(res["latency_ms"]["p50"], 2),
                "p99_ms": round(res["latency_ms"]["p99"], 2),
                "goodput_qps": res["goodput_qps"],
                "padding_waste": snap.get("padding_waste"),
                "occupancy": snap.get("occupancy"),
                "batches": snap.get("batches"),
                "engine_compile_s": round(compile_s, 2),
                "completed": res["completed"],
                "errors": res["errors"],
            }
        # int8 leg: quantize the SAME model (format_version 4 artifact),
        # serve it side-by-side with the f32 engines through the
        # dtype-routed bucket cache, and gate each bucket's top-1 delta
        # on flags.quant_accuracy_budget (BENCH_QUANT=0 skips)
        if os.environ.get("BENCH_QUANT", "1") == "1":
            try:
                leg["quant"] = _quant_serving_leg(
                    art, sym, args, aux, side, buckets, reqs_per_bucket)
            except Exception as e:
                leg["quant"] = _failed("serving.quant", e)
    finally:
        try:
            os.unlink(art)
        except OSError:
            pass
    return leg


def _quant_serving_leg(f32_art, sym, args, aux, side, buckets,
                       reqs_per_bucket):
    """Int8 post-training quantization leg of the serving benchmark.

    Calibrates on deterministic synthetic batches, freezes a
    ``format_version`` 4 artifact (tools/quantize_model.py is the same
    path as a CLI), then for every bucket runs ONE server holding the
    f32 and int8 engines side-by-side: the loadgen drives the int8
    engines (``dtype="int8"``), and the accuracy probe replays an
    identical probe set through BOTH engine families at the bucket's
    batch size so the reported top-1 delta is per-bucket (it sees that
    bucket's padding). The probe numbers are already host-side, so the
    ``quant/accuracy_delta`` gauge costs zero extra device syncs."""
    import tempfile
    import numpy as np
    from mxnet_tpu import quant, telemetry as _telemetry
    from mxnet_tpu.config import flags as _flags
    from mxnet_tpu.serve import Server
    from tools.serve_loadgen import measure, measure_accuracy

    rng = np.random.RandomState(1)
    calib = [{"data": rng.randn(8, 3, side, side).astype("f4")}
             for _ in range(4)]
    q_art = tempfile.mktemp(suffix=".int8.mxtpu")
    t0 = time.perf_counter()
    meta = quant.export_quantized(sym, args, aux, calib,
                                  {"data": (None, 3, side, side)}, q_art)
    rep = meta["quant"]
    wb = rep["weight_bytes"]
    out = {"export_s": round(time.perf_counter() - t0, 2),
           "artifact_bytes_f32": os.path.getsize(f32_art),
           "artifact_bytes_int8": os.path.getsize(q_art),
           "weight_payload_ratio": round(wb["int8"] / float(wb["f32"]), 3)
           if wb["f32"] else None,
           "sites": len(rep["sites"]),
           "skipped": len(rep["skipped"]),
           "calibration_fingerprint": rep["calibration"]["fingerprint"],
           "accuracy_budget": float(_flags.quant_accuracy_budget),
           "buckets": {}}
    gauge = _telemetry.gauge(
        "quant/accuracy_delta",
        "top-1 accuracy delta (f32 - int8) of the quantized serving "
        "engines on the bench probe set, labelled by bucket")
    try:
        for b in buckets:
            srv = Server(f32_art, quantized=q_art, buckets=(b,),
                         batch_timeout_ms=2)
            t1 = time.perf_counter()
            srv.model.engine_cache.engine(b, dtype="int8")
            compile_s = time.perf_counter() - t1
            # the probe replays through BOTH engine families; build the
            # f32 sibling up front too so compiles stay out of every
            # latency number
            srv.model.engine_cache.engine(b, dtype="f32")
            res = measure(srv, concurrency=b,
                          requests=reqs_per_bucket * b,
                          timeout_ms=600000, dtype="int8")
            probe = measure_accuracy(srv, srv, examples=4 * b, batch=b)
            snap = (srv.metrics().get("buckets_by_dtype", {})
                    .get("int8", {}).get(str(b), {}))
            srv.close(drain=True)
            delta = probe["top1_delta"]
            gauge.set(delta, bucket=str(b))
            out["buckets"][str(b)] = {
                "p50_ms": round(res["latency_ms"]["p50"], 2),
                "p99_ms": round(res["latency_ms"]["p99"], 2),
                "goodput_qps": res["goodput_qps"],
                "padding_waste": snap.get("padding_waste"),
                "batches": snap.get("batches"),
                "engine_compile_s": round(compile_s, 2),
                "completed": res["completed"],
                "errors": res["errors"],
                "top1_delta": delta,
                "agreement": probe["agreement"],
                "accuracy_ok": delta <= float(_flags.quant_accuracy_budget),
            }
    finally:
        try:
            os.unlink(q_art)
        except OSError:
            pass
    return out


def _make_rec(n_images, side):
    """Generate (once, cached) a synthetic-ImageNet .rec of JPEG noise."""
    import tempfile
    import cv2
    import numpy as np
    from mxnet_tpu import recordio
    path = os.path.join(tempfile.gettempdir(),
                        "mxtpu_bench_%d_%d.rec" % (n_images, side))
    idx = os.path.splitext(path)[0] + ".idx"
    if os.path.exists(path) and os.path.exists(idx):
        return path
    rng = np.random.RandomState(0)
    w = recordio.MXIndexedRecordIO(idx, path, "w")
    for i in range(n_images):
        img = rng.randint(0, 255, (side, side, 3), dtype=np.uint8)
        ok, buf = cv2.imencode(".jpg", img, [cv2.IMWRITE_JPEG_QUALITY, 95])
        w.write_idx(i, recordio.pack(
            recordio.IRHeader(0, float(i % 1000), i, 0), buf.tobytes()))
    w.close()
    return path


def _data_leg(ctx, batch, n_images=512, side=144, shards=4):
    """Streaming data tier throughput (docs/data.md): decode+augment
    delivery rate of StreamingDataIter over a make_recordio-packed
    synthetic shard set. Host-side only — batches are consumed, never
    shipped to the device — so the number is pipeline rate, not link
    rate."""
    import numpy as np
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    try:
        from make_recordio import iter_synth_images, shard_paths, \
            write_shards
    finally:
        sys.path.pop(0)
    from mxnet_tpu.data import (ImageDecoder, ShardedRecordStream,
                                StreamingDataIter)
    import tempfile
    prefix = os.path.join(tempfile.gettempdir(), "mxtpu_bench_data",
                          "synth_%d_%d" % (n_images, side))
    recs = shard_paths(prefix, shards)
    if not all(os.path.exists(r) for r in recs):
        recs = write_shards(
            iter_synth_images(n_images, side=side), prefix, shards)
    stream = ShardedRecordStream(recs, shuffle=True, seed=0)
    it = StreamingDataIter(
        stream, ImageDecoder((3, 128, 128), rand_crop=True,
                             rand_mirror=True),
        batch_size=batch, ctx=ctx)
    try:
        # warm epoch: thread spin-up + page cache, then the timed one
        for _ in it:
            pass
        it.reset()
        n = 0
        t0 = time.perf_counter()
        for b in it:
            n += b.data[0].shape[0]
        dt = time.perf_counter() - t0
        depth = it.queue_depth() if hasattr(it, "queue_depth") else None
        return {
            "examples_per_s": round(n / dt, 1),
            "records": stream.records_per_epoch(),
            "shards": len(recs),
            "decode_threads": it._nthreads,
            "queue_depth": depth,
        }
    finally:
        it.close()


class _OneBatchIter:
    """Reference --benchmark 1 semantics: one device-resident batch,
    repeated; zero input-pipeline cost so the step program is what's
    measured."""

    def __init__(self, batch, steps, provide_data, provide_label):
        self._batch = batch
        self._steps = steps
        self.provide_data = provide_data
        self.provide_label = provide_label
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


def _dist_kv_us(n=2, size_mb=1.0):
    """kvstore push/pull µs with a REAL network leg: a 2-process
    tools/launch.py group on host CPUs (label: kv_type=dist_sync)."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    # this process holds the chip: the workers are CPU workers by name,
    # in the environment (so launch.py lets them start beside the chip)
    # and on their own command line
    env["JAX_PLATFORMS"] = "cpu"
    here = os.path.dirname(os.path.abspath(__file__))
    r = subprocess.run(
        [sys.executable, os.path.join(here, "tools", "launch.py"),
         "-n", str(n), sys.executable,
         os.path.join(here, "tools", "bandwidth.py"),
         "--kv-type", "dist_sync", "--platform", "cpu",
         "--size-mb", str(size_mb)],
        capture_output=True, text=True, timeout=600, env=env, cwd=here)
    vals = []
    for line in r.stdout.splitlines():
        _, _, payload = line.partition("{")
        if '"kvstore_push_pull_us"' in line:
            vals.append(json.loads("{" + payload)["value"])
    if not vals:
        raise RuntimeError("no worker reported: %s" % r.stdout[-500:])
    return round(sum(vals) / len(vals), 1)


def main():
    want_cpu = os.environ.get("BENCH_PLATFORM", "") == "cpu"
    import jax
    if want_cpu:
        jax.config.update("jax_platforms", "cpu")
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not want_cpu:
        print("bench: JAX found no TPU (devices: %s). A benchmark number "
              "comes from the chip; BENCH_PLATFORM=cpu asks for a CPU smoke "
              "run by name." % (jax.devices(),), file=sys.stderr)
        sys.exit(1)

    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import models
    from mxnet_tpu import perfmodel as _perfmodel
    from mxnet_tpu.config import flags as _flags
    from mxnet_tpu.io import DataBatch, DataDesc

    ctx = mx.tpu() if on_tpu else mx.cpu()
    batch = 128 if on_tpu else 8  # CPU smoke size
    steps = 30 if on_tpu else 3

    sym = models.resnet_symbol(num_classes=1000, num_layers=50)
    rng = np.random.RandomState(0)
    data_nd = mx.nd.array(rng.randn(batch, 3, 224, 224).astype(np.float32),
                          ctx=ctx)
    label_nd = mx.nd.array(rng.randint(0, 1000, (batch,)).astype(np.float32),
                           ctx=ctx)
    it = _OneBatchIter(
        DataBatch(data=[data_nd], label=[label_nd]), steps,
        [DataDesc("data", (batch, 3, 224, 224))],
        [DataDesc("softmax_label", (batch,))])

    mod = mx.mod.Module(sym, context=ctx)

    def force():
        # host fetch: cannot return before the whole dependency chain ran
        arr = mod._exec.arg_dict[mod._param_names[0]]._data
        return float(np.asarray(jax.device_get(arr)).ravel()[0])

    def timing_cb(lst):
        # epoch-end probe shared by every measured fit(): force a host
        # fetch, then stamp
        def cb(epoch, symbol, arg_p, aux_p):
            force()
            lst.append(time.perf_counter())
        return cb

    # seed the run-wide telemetry registry (docs/observability.md): with
    # flops known, fit()'s window sampling publishes a live train/mfu
    # gauge; the analytic estimate is refined from XLA cost analysis below
    from mxnet_tpu import telemetry as _telemetry
    _telemetry.set_run_info(
        flops_per_step=_perfmodel.RESNET50_TRAIN_FLOPS_PER_IMG * batch,
        device_kind=dev.device_kind, batch_size=batch)

    times = []
    epoch_cb = timing_cb(times)

    # epoch 0 = warmup/compile; epochs 1..2 timed (through Module.fit)
    mod.fit(it, num_epoch=3, eval_metric=None, kvstore="tpu_sync",
            optimizer="sgd",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9,
                              "multi_precision": True},
            initializer=mx.initializer.Xavier(factor_type="in",
                                              magnitude=2.0),
            epoch_end_callback=epoch_cb)
    if mod._fused is None:
        raise RuntimeError("tpu_sync did not engage the fused train step — "
                           "bench would measure the eager path")
    dt = times[-1] - times[0]
    n_timed = steps * (len(times) - 1)
    img_s = batch * n_timed / dt
    step_ms = dt / n_timed * 1e3

    # cross-check: fully synchronous per-step latency (fetch every step).
    # An async-dispatch bug shows up as sync_step_ms >> step_ms.
    n_sync = 5 if on_tpu else 1
    batch_obj = it._batch
    t1 = time.perf_counter()
    for _ in range(n_sync):
        # same donating program fit() used (a bare forward_backward would
        # trigger a second multi-minute XLA compile of the non-donating
        # variant for no measurement benefit)
        mod._fit_step(batch_obj)
        force()
    sync_step_ms = (time.perf_counter() - t1) / n_sync * 1e3

    # FLOPs/step from XLA cost analysis of the compiled fused program
    flops_per_step = _perfmodel.RESNET50_TRAIN_FLOPS_PER_IMG * batch
    try:
        ex = mod._exec
        cost = mod._fused.cost_analysis(ex._arg_vals(), ex._aux_vals(),
                                        mod._fused_opt_state)
        if cost and cost.get("flops", 0) > 0:
            flops_per_step = float(cost["flops"])
    except Exception as e:
        _failed("flops_per_step", e)
    _telemetry.set_run_info(flops_per_step=flops_per_step)

    # mxlint Layer-2 metrics of the exact benched step program (convert
    # count, donation coverage, d2h count) so BENCH_*.json tracks the
    # lint health of the hot path alongside its throughput
    mxlint_metrics = None
    try:
        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "tools"))
        try:
            from diagnose_step_hlo import lower_step
        finally:
            sys.path.pop(0)
        from mxnet_tpu.analysis import hlo_passes
        mxlint_metrics = hlo_passes.metrics_from_text(
            lower_step(mod, donate=True).as_text())
    except Exception as e:
        mxlint_metrics = _failed("mxlint", e)

    # Layer-3 concurrency census: how many MXL6xx findings the codebase
    # carries right now, per rule (baselined debt INCLUDED — the lint
    # gate tracks growth, the census tracks the absolute count so
    # BENCH_*.json shows the debt being paid down across PRs)
    try:
        from mxnet_tpu.analysis import runner as _lint_runner
        _res = _lint_runner.run(
            ["mxnet_tpu"], baseline_path=None,
            root=os.path.dirname(os.path.abspath(__file__)),
            enabled=frozenset(["MXL601", "MXL602", "MXL603",
                               "MXL604", "MXL605", "MXL606"]))
        census = {}
        for d in _res.diags:
            census[d.rule] = census.get(d.rule, 0) + 1
        census = dict(sorted(census.items()))
        if isinstance(mxlint_metrics, dict):
            mxlint_metrics["concurrency_census"] = census
        else:
            mxlint_metrics = {"step_hlo": mxlint_metrics,
                              "concurrency_census": census}
    except Exception as e:
        census = _failed("mxlint.concurrency_census", e)
        if isinstance(mxlint_metrics, dict):
            mxlint_metrics["concurrency_census"] = census

    # kernel-tier dispatch report: which ops the Pallas tier took over in
    # the traced program (counters accumulate from the module bind/trace
    # in this process), tuner hit/miss split, and the tuning-cache
    # fingerprint so BENCH_*.json lines are attributable to a specific
    # set of tuned configs (docs/tuning.md)
    kernel_tier_report = None
    try:
        from mxnet_tpu.kernels import tier as _ktier
        from mxnet_tpu.tune import cache as _tcache
        st = _ktier.stats()
        tcache = _tcache.get_default()
        kernel_tier_report = {
            "tier": st["tier"],
            "dispatch": dict(st["dispatch"]),
            "fallback": dict(st["fallback"]),
            "tuner_hits": st["tuner_hits"],
            "tuner_misses": st["tuner_misses"],
            "configs": {k: dict(v) for k, v in st["configs"].items()},
            "tuning_cache": {"entries": len(tcache.entries),
                             "version_ok": tcache.version_ok,
                             "fingerprint": tcache.fingerprint()},
        }
    except Exception as e:
        kernel_tier_report = _failed("kernel_tier", e)

    # ---- streaming data tier (BENCH_DATA=0 skips): decode+augment
    # delivery rate of the sharded streaming pipeline (mxnet_tpu/data/,
    # docs/data.md) over a make_recordio-packed synthetic set, plus the
    # headline fit's input-stall telemetry. Host-side only — no extra
    # device traffic — so it runs on CPU rounds too.
    data_pipeline = None
    if os.environ.get("BENCH_DATA", "1") == "1":
        try:
            data_pipeline = _data_leg(ctx, batch)
        except Exception as e:
            data_pipeline = _failed("data_pipeline", e)
    # input-stall attribution of the benched fit (published by fit's
    # window telemetry from host-held timers — docs/observability.md)
    input_stall_ms = stall_frac = None
    try:
        from mxnet_tpu.telemetry import registry as _treg
        g = _treg.default_registry().get("data/input_stall_ms")
        input_stall_ms = g.value() if g is not None else None
        g = _treg.default_registry().get("data/stall_frac")
        stall_frac = g.value() if g is not None else None
    except Exception as e:
        _failed("input_stall_ms", e)

    # ---- real-data variant (OPT-IN: BENCH_RECORDIO=1): threaded RecordIO
    # pipeline feeding the same fused module (decode+augment+H2D overlapped
    # with training). Reported as extra fields: recordio_img_s and
    # recordio_overlap (achieved / min(input-only rate, compute rate) —
    # 1.0 means the pipeline fully hides input prep). The pipeline's own
    # throughput/overlap is covered host-side by
    # tests/test_image_record_iter.py.
    recordio_img_s = recordio_overlap = input_only_img_s = None
    if on_tpu and os.environ.get("BENCH_RECORDIO", "0") == "1":
        from mxnet_tpu.io import ImageRecordIter
        rec = _make_rec(n_images=768, side=256)
        rit = ImageRecordIter(rec, data_shape=(3, 224, 224),
                              batch_size=batch, rand_crop=True,
                              rand_mirror=True, scale=1.0,
                              preprocess_threads=max(os.cpu_count() or 2, 2),
                              prefetch_buffer=4, ctx=ctx, seed=1)
        # input-only rate (decode+augment+device_put, no training)
        n_in = 0
        t0 = time.perf_counter()
        for b in rit:
            jax.block_until_ready(b.data[0]._data)
            n_in += batch
        np.asarray(jax.device_get(b.data[0]._data[0, 0, 0, :1]))
        input_only_img_s = n_in / (time.perf_counter() - t0)
        rit.reset()
        # overlapped: same module, fused step, real batches
        t_rec = []
        mod.fit(rit, num_epoch=3, eval_metric=None, kvstore="tpu_sync",
                optimizer="sgd",
                optimizer_params={"learning_rate": 0.05, "momentum": 0.9,
                                  "multi_precision": True},
                epoch_end_callback=timing_cb(t_rec))
        steps_per_epoch = 768 // batch
        dt_rec = t_rec[-1] - t_rec[0]
        recordio_img_s = batch * steps_per_epoch * (len(t_rec) - 1) / dt_rec
        recordio_overlap = recordio_img_s / min(input_only_img_s, img_s)
        rit.close()

    mfu = 0.0
    if on_tpu:
        mfu = (img_s / batch) * flops_per_step / _perfmodel.peak_flops(dev.device_kind)
        # A broken harness must fail loudly, not record an impossible number
        # (raise, not assert: asserts vanish under python -O).
        if not 0.0 < mfu <= 1.0:
            raise RuntimeError(
                "measured MFU %.3f is outside (0, 1] — timing harness is "
                "not measuring execution (step_ms=%.2f sync_step_ms=%.2f)"
                % (mfu, step_ms, sync_step_ms))

    out = {
        # a CPU smoke run (asked for by name) never carries the name of
        # the device metric
        "metric": ("resnet50_module_fit_img_per_sec_b%d_bf16" % batch
                   if on_tpu else "resnet50_module_fit_cpu_smoke_b%d" % batch),
        "platform": dev.platform,
        "value": round(img_s, 2),
        "unit": "img/s",
        "vs_baseline": round(img_s / BASELINE_IMG_S, 3),
        "mfu": round(mfu, 4),
        "step_ms": round(step_ms, 3),
        "sync_step_ms": round(sync_step_ms, 3),
        # host-side cost hidden by async dispatch: per-step latency when
        # the host waits on every step minus the pipelined per-step time.
        # ~0 means dispatch is compute-bound.
        "host_overhead_ms": round(max(0.0, sync_step_ms - step_ms), 3),
        "engine_depth": int(_flags.engine_depth),
        "device": dev.device_kind,
        "flops_per_step": flops_per_step,
    }
    if mxlint_metrics is not None:
        out["mxlint"] = mxlint_metrics
    if kernel_tier_report is not None:
        out["kernel_tier"] = kernel_tier_report
    if recordio_img_s is not None:
        out["recordio_img_s"] = round(recordio_img_s, 2)
        out["recordio_input_only_img_s"] = round(input_only_img_s, 2)
        out["recordio_overlap"] = round(recordio_overlap, 3)
    if data_pipeline is not None:
        out["data_pipeline"] = data_pipeline
    if input_stall_ms is not None:
        out["input_stall_ms"] = round(float(input_stall_ms), 3)
    if stall_frac is not None:
        out["stall_frac"] = round(float(stall_frac), 4)
    # the other two BASELINE.json metrics (kvstore push/pull µs, Gluon
    # LSTM tokens/sec) ride along as extra fields; BENCH_EXTRA=0 skips
    if os.environ.get("BENCH_EXTRA", "1") == "1":
        _secondary_legs(out, on_tpu)

    # end-of-run registry snapshot: the BENCH_*.json line carries the
    # same step-time/MFU/engine-depth/kernel-dispatch series an operator
    # would scrape from the Prometheus endpoint mid-run
    try:
        out["telemetry"] = _telemetry.snapshot()
    except Exception as e:
        out["telemetry"] = _failed("telemetry", e)

    print(json.dumps(out))
    if _FAILED:
        print("bench: legs failed: %s" % ", ".join(_FAILED), file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
