"""Runner of the kind ``train_lm_fit``: one cell of ``Module.fit`` training
of a language model on token batches.

It is ``runners/train_fit.py``'s run with what a language model changes:
token batches (``harness/token_traffic.py``), analytic FLOPs a sequence
(``harness/flops_lm.py``), a comparison made for Adam and for discrete
routing (``harness/compare_lm.py``), and a model too large to keep twice
on the chip. One ``fit()`` call is set-up and window both: epoch 0 is the
warm-up (it compiles, and its first three steps are the ones the reference
follows), epoch 1 is the window. ``train_img_per_s`` is samples a second,
and one sample is one sequence of ``sequence_length`` tokens.

Memory: the runner keeps no float32 copy of the parameters on the chip
beside the module's. The initial weights go to host memory leaf by leaf
as they are made; Adam's first moment after step 1 and the weights after
step 3 are fetched to host memory during the warm-up epoch; the reference
runs after the module is freed, with its state donated from step to step.
"""
from __future__ import annotations

import contextlib
import gc
import importlib
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import compare, compare_lm, flops_lm, manifest as manifest_, \
    peaks, scopes, token_traffic, xplane
from runners import train_fit
from runners.train_fit import Window, devices_for, memory_peak_bytes

TRACE_START_STEP = 3       # steps into the window before the trace starts
TRACE_STEPS = 8            # steps traced: a step is most of a second


def symbol_kwargs(cfg):
    """The builder's keyword arguments from the configuration's own keys
    (the published ``config.json`` names)."""
    lin = cfg["linear_attn_config"]
    kw = {k: cfg[k] for k in (
        "hidden_size", "num_attention_heads", "kda_gate_low_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
        "intermediate_size", "moe_intermediate_size", "num_experts_per_token",
        "routed_scaling_factor", "rms_norm_eps", "first_k_dense_replace")}
    kw.update(kda_num_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
              short_conv_kernel_size=lin["short_conv_kernel_size"],
              kda_layers=tuple(lin["kda_layers"]),
              num_experts=cfg["num_experts_published"],
              layers=tuple(cfg["layers"]),
              experts_held=tuple(cfg["experts_held"]),
              vocab_rows=cfg["vocab_size"])
    return kw


class TokenWindowIter(train_fit.WindowIter):
    """The mix's batches in turn: ``warmup_steps`` of them in epoch 0, then
    for ``seconds`` from the stamp that ends the warm-up. In the warm-up it
    fetches to host memory what the comparison reads of the timed module:
    every token's loss of steps 1 to 3, Adam's first moment after step 1,
    the weights and the expert layers' counters after step 3."""

    def __init__(self, batches, descs, warmup_steps, win, mod):
        super().__init__(batches, descs, warmup_steps, 1, win, mod)

    def _snapshot(self, i):
        win, mod = self._win, self._mod
        if 1 <= i <= 3:
            win.snap_outputs[i] = mod.get_outputs()[0].asnumpy()
        if i == 1:
            # the buffers are the next step's to donate: read them now
            win.snap_params["m1"] = {
                k: np.asarray(st[0])
                for k, st in mod._fused_opt_state.items()}
        if i == 3:
            win.snap_params["counters3"] = {
                k: v.asnumpy() for k, v in mod._exec.aux_dict.items()}
            win.snap_params["w3"] = {
                k: mod._exec.arg_dict[k].asnumpy()
                for k in mod._fused.param_names}

    def __next__(self):
        with jax.profiler.TraceAnnotation("bench/next"):
            i, win = self._i, self._win
            if self._epoch == 0:
                # `fit` asks for batch i after it has dispatched step i
                self._snapshot(i)
                if i >= self._warmup_steps:
                    raise StopIteration
            else:
                if win.trace_dir is not None:
                    if i == TRACE_START_STEP and not win.tracing:
                        opts = jax.profiler.ProfileOptions()
                        opts.python_tracer_level = 0
                        jax.profiler.start_trace(win.trace_dir,
                                                 profiler_options=opts)
                        win.tracing = True
                    elif i == TRACE_START_STEP + TRACE_STEPS and win.tracing:
                        jax.profiler.stop_trace()
                        win.tracing = False
                if time.perf_counter() >= win.t0 + win.seconds:
                    raise StopIteration
                win.steps += 1
            self._i += 1
            return self._batches[i % len(self._batches)]


def build(cell, seed, seconds, trace_dir, devices):
    """Everything up to the ``fit`` call. The initial weights are made on
    the chip leaf by leaf, copied to host memory, and the chip's copy is
    the one the module adopts."""
    import mxnet_tpu as mx
    from mxnet_tpu.io import DataBatch, DataDesc

    cfg, mix = cell["cfg"], cell["mix"]
    if cfg["steps_per_dispatch"] != 1 or len(devices) != 1:
        raise ValueError("train_lm_fit runs the per-step program on one chip")
    if mix["warmup_steps"] < 4:
        raise ValueError("warmup_steps must be at least 4")
    ref = importlib.import_module("references." + cfg["reference"])
    # the symbol first: a program without this model fails here, at once
    builder_mod, builder_fn = cfg["symbol"]["builder"].rsplit(".", 1)
    sym = getattr(importlib.import_module(builder_mod), builder_fn)(
        **symbol_kwargs(cfg))
    want = set(sym.list_arguments()) - {"data", "softmax_label"}
    if want != set(ref.param_shapes(cfg)):
        raise ValueError("the symbol's variables are not the reference's: "
                         "%s" % sorted(want ^ set(ref.param_shapes(cfg)))[:6])

    with jax.default_device(devices[0]):
        given = ref.init_params(cfg, seed)
        w0 = {k: np.asarray(v) for k, v in given.items()}
        batches = token_traffic.make_token_batches(mix, cfg, seed)

    dev = devices[0]
    ctx = mx.tpu(dev.id) if dev.platform == "tpu" else mx.cpu(dev.id)
    nd = mx.nd.NDArray
    host = mix["placement"] == "host"
    feed = [DataBatch(
        data=[mx.nd.array(d, ctx=mx.cpu()) if host else nd(d, ctx=ctx)],
        label=[mx.nd.array(l, ctx=mx.cpu()) if host else nd(l, ctx=ctx)])
        for d, l in batches]
    descs = ([DataDesc("data", tuple(batches[0][0].shape))],
             [DataDesc("softmax_label", tuple(batches[0][1].shape))])
    aux = {k: jnp.zeros(s, jnp.float32, device=dev) for k, s in zip(
        sym.list_auxiliary_states(),
        sym.infer_shape(data=descs[0][0].shape,
                        softmax_label=descs[1][0].shape)[2])}
    mod = mx.mod.Module(sym, context=ctx)
    win = Window(seconds, trace_dir)
    it = TokenWindowIter(feed, descs, mix["warmup_steps"], win, mod)
    return {"mx": mx, "mod": mod, "it": it, "win": win, "ref": ref,
            "w0": w0, "given": (given, aux), "batches": batches,
            "ctxs": [ctx]}


def held_counts(cfg, choices):
    """expert layer -> the assignments that fell to the experts held."""
    lo, hi = cfg["experts_held"]
    return {"l%d" % l: float(jnp.sum((c >= lo) & (c < hi)))
            for l, c in choices.items()}


def program_readings(cfg, win):
    counters = win.snap_params["counters3"]
    return {"loss_rows": [win.snap_outputs[i] for i in (1, 2, 3)],
            "m1": win.snap_params["m1"], "w3": win.snap_params["w3"],
            # the state is [steps, a step's mean held, busiest]
            "held3": {k.split("_")[0]: float(v[0] * v[1])
                      for k, v in counters.items()}}


_REF_STEPS = {}


@contextlib.contextmanager
def _out_of_the_persistent_cache():
    """What compiles inside is read from JAX's persistent cache where it
    is there and never written to it. The machine with the chip caps that
    cache (``JAX_COMPILATION_CACHE_MAX_SIZE``, 192 MiB, least recently used
    out first) and the step's entry alone is 188 MB: the reference's,
    written after it, pushed it out, and every run compiled both. With the
    reference kept out, a run after the first reads the step: ``setup_s``
    54 to 60 s where it was 227 to 235 (PERF.md, PR 27). The step's compile
    is inside ``setup_s``; the reference's is not."""
    name = "jax_persistent_cache_min_compile_time_secs"
    was = getattr(jax.config, name)
    jax.config.update(name, float("inf"))
    try:
        yield
    finally:
        jax.config.update(name, was)


def reference_readings(ref, cfg, w0, batches, operand=None, fault=None):
    """The plain reference through the first three steps, its state on the
    chip and donated from step to step; what ``compare_lm.numbers`` reads
    comes back to host memory. ``operand`` computes it in a lower precision
    (the control), ``fault`` plants a wrong layer by name."""
    import json
    key = (ref.__name__, json.dumps(cfg, sort_keys=True), operand, fault)
    if key not in _REF_STEPS:
        _REF_STEPS[key] = jax.jit(
            lambda p, m, v, t, d, l: ref.train_step(
                cfg, p, m, v, t, d, l, operand=operand, fault=fault),
            donate_argnums=(0, 1, 2))
    step = _REF_STEPS[key]
    p = {k: jnp.asarray(v) for k, v in w0.items()}
    m = {k: jnp.zeros(v.shape, jnp.float32) for k, v in w0.items()}
    v = {k: jnp.zeros(a.shape, jnp.float32) for k, a in w0.items()}
    out = {"loss_rows": [], "held3": {}}
    for i in range(3):              # the batches in the iterator's order
        data, label = batches[i % len(batches)]
        with _out_of_the_persistent_cache():
            rows, choices, p, m, v = step(p, m, v, i + 1, data, label)
        out["loss_rows"].append(np.asarray(rows))
        for l, n in held_counts(cfg, choices).items():
            out["held3"][l] = out["held3"].get(l, 0.0) + n
        if i == 0:
            out["m1"] = {k: np.asarray(a) for k, a in m.items()}
            out["choices1"] = {l: np.asarray(c) for l, c in choices.items()}
    out["w3"] = {k: np.asarray(a) for k, a in p.items()}
    return out


def drive(cell, seed, seconds, trace_dir, devices):
    """Build, fit, read what the program produced, free its state."""
    b = build(cell, seed, seconds, trace_dir, devices)
    train_fit.fit(cell, b)
    win, mod = b["win"], b["mod"]
    stats = [d.memory_stats() or {} for d in devices]
    # {gauge: a step's mean}; a program without such counters gives none
    counters = {k: v for k, (v, _) in mod._op_counters().items()} \
        if hasattr(mod, "_op_counters") else {}
    hlo_text, hlo_text_s = None, None
    if trace_dir is not None:
        # the text of the executable that `fit` compiled, for the scopes of
        # its instructions (harness/scopes.py). No second compile: the same
        # arguments trace to the same jaxpr, whose lowering JAX holds with
        # its executable (`hlo_text_s` says what it took)
        ex = mod._exec
        t = time.perf_counter()
        hlo_text = mod._fused.lower(
            ex._arg_vals(), ex._aux_vals(), mod._fused_opt_state,
            met_state=mod._fused_met_state, donate=True).compile().as_text()
        hlo_text_s = time.perf_counter() - t
    out = {"win": win, "ref": b["ref"], "w0": b["w0"],
           "batches": [(jnp.asarray(d), jnp.asarray(l))
                       for d, l in b["batches"]],
           "memory_peak": max(memory_peak_bytes(s) for s in stats),
           "memory_stats": stats[0], "counters": counters, "hlo_text": hlo_text,
           "hlo_text_s": hlo_text_s,
           "prog": program_readings(cell["cfg"], win)}
    # free the program's state before the reference takes the chip
    b.clear()
    del mod
    gc.collect()
    return out


def run(cell, args, t_start):
    bench_dir = cell["bench_dir"]
    cfg = cell["cfg"]
    devices = devices_for(cell, args.rehearse_cpu)

    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(cell["root"], ".bench_out", "trace",
                                 cell["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
    d = drive(cell, args.seed, args.seconds, trace_dir, devices)
    win = d["win"]
    setup_s = win.t0 - t_start
    window_s = win.t1 - win.t0
    samples_per_s = win.steps * cfg["batch_size"] / window_s

    t_ref = time.perf_counter()
    ref = reference_readings(d["ref"], cfg, d["w0"], d["batches"])
    ref_s = time.perf_counter() - t_ref
    nums = compare_lm.numbers(d["prog"], ref, d["w0"])
    correct, rows = compare.judge(nums, cfg["limits"])

    metrics, breakdown, device_extra = {}, None, {}
    if args.trace:
        trace = xplane.load(trace_dir)
        ctx = {"trace": trace, "hlo_text": d["hlo_text"], "cfg": cfg,
               "step_program": cfg["step_program"],
               "steps_per_program": 1,
               "batch_size": cfg["batch_size"], "chips": cell["chips"],
               "train_flops_per_image": flops_lm.train_flops_per_sample(cfg),
               "peaks": None if args.rehearse_cpu
               else peaks.peaks(devices[0].device_kind),
               "counters": dict({"window_steps": win.steps},
                                **d["counters"])}
        if trace["devices"]:
            for m in manifest_.metrics_of(cell["manifest"], "per_layer",
                                          cell["name"]):
                value = manifest_.layer_reader(bench_dir, m["name"])(ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            summary = xplane.device_summary(trace, cfg["step_program"])
            if summary:
                device_extra = {"busy_s": summary[0], "window_s": summary[1]}
            breakdown = {
                "device_ops": xplane.top_ops(trace, cfg["step_program"]),
                "idle_gaps": xplane.idle_gaps(trace, cfg["step_program"])}
            if scopes.scope_ms(ctx):
                breakdown["scope_ms_per_step"] = scopes.scope_ms(ctx)
                breakdown["scope_top_ops"] = scopes.scope_top_ops(ctx)
        if not os.environ.get("BENCH_KEEP_TRACE"):   # for a look by hand
            shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        values = {"train_img_per_s": samples_per_s, "setup_s": setup_s}
        for m in manifest_.metrics_of(cell["manifest"], "end_to_end",
                                      cell["name"]):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}

    dev = jax.devices()[0]
    result = {
        "correct": bool(correct), "attempted": win.steps, "failed": 0,
        "metrics": metrics,
        "device": dict({"platform": dev.platform, "kind": dev.device_kind,
                        "count": jax.device_count(),
                        "memory_peak_bytes": d["memory_peak"]}, **device_extra),
    }
    if breakdown:
        result["breakdown"] = breakdown
    result["window"] = {"steps": win.steps, "seconds": window_s,
                        "tokens_per_s": samples_per_s
                        * cfg["sequence_length"],
                        "reference_s": ref_s, "hlo_text_s": d["hlo_text_s"],
                        "counters": d["counters"],
                        "memory_stats": d["memory_stats"]}
    result["compared"] = {name: {"value": value, "limit": limit}
                          for name, value, limit in rows}
    for name, (value, detail) in sorted(nums.items()):
        limit = cfg["limits"].get(name)
        print("compared %-12s %.6g  limit %s  (%s)" % (
            name, value, "none" if limit is None else "%.6g" % limit,
            detail), file=sys.stderr)
    return result
