"""Runner of the kind ``train_lm_cfg``: ``runners/train_lm_fit.py``'s run
(one cell of ``Module.fit`` training of a language model on token batches:
its iterator, its snapshots, its reference readings, its comparison) with
everything that names a model taken from the configuration file and its
reference, so that the next language model brings a file and no runner:

* ``symbol.builder`` with its keyword arguments: the configuration's keys
  listed under ``symbol.keys`` by their own names, and those under
  ``symbol.renamed`` (builder's argument -> configuration's key);
* ``flops``: the module of ``harness/`` whose ``train_flops_per_sample(cfg)``
  counts a sample's operations;
* ``device_scopes``: the named scopes the breakdown and the per-layer
  readers split the step's device time by (``harness/scopes_of.py``).
"""
from __future__ import annotations

import gc
import importlib
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import compare, compare_lm, manifest as manifest_, peaks, \
    scopes_of, token_traffic, xplane
from runners import train_fit
from runners.train_fit import Window, devices_for, memory_peak_bytes
from runners.train_lm_fit import TokenWindowIter, program_readings, \
    reference_readings


def symbol_kwargs(cfg):
    """The builder's keyword arguments, as the configuration maps them."""
    how = cfg["symbol"]
    kw = {k: cfg[k] for k in how["keys"]}
    kw.update({arg: cfg[key] for arg, key in how.get("renamed", {}).items()})
    return {k: tuple(v) if isinstance(v, list) else v for k, v in kw.items()}


def build_symbol(cfg):
    """The training symbol; a program without this model's builder ends
    the run here, at once, with exit code 1."""
    builder_mod, builder_fn = cfg["symbol"]["builder"].rsplit(".", 1)
    try:
        builder = getattr(importlib.import_module(builder_mod), builder_fn)
    except (ImportError, AttributeError) as e:
        train_fit._fail("this program has no builder %s (%s: %s)"
                        % (cfg["symbol"]["builder"], type(e).__name__, e))
    return builder(**symbol_kwargs(cfg))


def build(cell, seed, seconds, trace_dir, devices):
    """Everything up to the ``fit`` call, as ``train_lm_fit.build`` does
    it: the initial weights made on the chip leaf by leaf and copied to
    host memory, the chip's copy the one the module adopts."""
    import mxnet_tpu as mx
    from mxnet_tpu.io import DataBatch, DataDesc

    cfg, mix = cell["cfg"], cell["mix"]
    if cfg["steps_per_dispatch"] != 1 or len(devices) != 1:
        raise ValueError("train_lm_cfg runs the per-step program on one chip")
    if mix["warmup_steps"] < 4:
        raise ValueError("warmup_steps must be at least 4")
    sym = build_symbol(cfg)      # first: no model, no run
    ref = importlib.import_module("references." + cfg["reference"])
    want = set(sym.list_arguments()) - {"data", "softmax_label"}
    if want != set(ref.param_shapes(cfg)):
        raise ValueError("the symbol's variables are not the reference's: "
                         "%s" % sorted(want ^ set(ref.param_shapes(cfg)))[:6])

    dev = devices[0]
    with jax.default_device(dev):
        given = ref.init_params(cfg, seed)
        w0 = {k: np.asarray(v) for k, v in given.items()}
        batches = token_traffic.make_token_batches(mix, cfg, seed)

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


def drive(cell, seed, seconds, trace_dir, devices):
    """Build, fit, read what the program produced, free its state."""
    b = build(cell, seed, seconds, trace_dir, devices)
    train_fit.fit(cell, b)
    win, mod = b["win"], b["mod"]
    stats = [d.memory_stats() or {} for d in devices]
    counters = {k: v for k, (v, _) in mod._op_counters().items()}
    hlo_text, hlo_text_s = None, None
    if trace_dir is not None:
        # the text of the executable that `fit` compiled (no second
        # compile: the same arguments trace to the same jaxpr)
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
           "memory_stats": stats[0], "counters": counters,
           "hlo_text": hlo_text, "hlo_text_s": hlo_text_s,
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
    flops = importlib.import_module("harness." + cfg["flops"])

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
               "train_flops_per_image": flops.train_flops_per_sample(cfg),
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
            if scopes_of.scope_ms(ctx):
                breakdown["scope_ms_per_step"] = scopes_of.scope_ms(ctx)
                breakdown["scope_top_ops"] = scopes_of.scope_top_ops(ctx)
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
