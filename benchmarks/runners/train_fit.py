"""Runner of the kind ``train_fit``: one cell of ``Module.fit`` training.

One ``fit()`` call is set-up and window both: epoch 0 is the warm-up (it
compiles or reads the cache, and its first three steps are the ones the
reference follows), its end callback fetches a parameter to the host and
starts the clock, epoch 1 is the window, and its end callback's fetch
stops the clock. The same module, the same compiled step and the same
iterator serve both, so what is compared is what is timed.
"""
from __future__ import annotations

import gc
import importlib
import json
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from harness import compare, flops, manifest as manifest_, peaks, traffic, \
    xplane

TRACE_START_STEP = 6       # steps into the window before the trace starts
TRACE_STEPS = 24           # steps traced


class Window:
    """What the iterator, the callbacks and the runner share."""

    def __init__(self, seconds, trace_dir):
        self.seconds = seconds
        self.trace_dir = trace_dir
        self.t0 = self.t1 = None
        self.steps = 0            # batches yielded in the window
        self.snap_outputs = {}    # step -> softmax output of that step
        self.snap_params = {}     # step -> (args, aux) after that step
        self.tracing = False
        self.compiles_in_window = 0


class WindowIter:
    """The mix's batches in turn: ``warmup_steps`` of them in epoch 0,
    then for ``seconds`` from the stamp that ends the warm-up. It looks at
    the clock only where the batches yielded are a multiple of ``k``, so
    that ``fit`` never sees a tail group (which would go through, and
    compile, another program)."""

    def __init__(self, batches, descs, warmup_steps, k, win, mod):
        self._batches = batches
        self.provide_data, self.provide_label = descs
        self.batch_size = self.provide_data[0].shape[0]
        self._warmup_steps = warmup_steps
        self._k = k
        self._win = win
        self._mod = mod
        self._epoch = 0
        self._i = 0

    def __iter__(self):
        return self

    def reset(self):
        self._epoch += 1
        self._i = 0

    def __next__(self):
        with jax.profiler.TraceAnnotation("bench/next"):
            i, win = self._i, self._win
            if self._epoch == 0:
                # `fit` asks for batch i after it has dispatched step i
                if 1 <= i <= 3:
                    win.snap_outputs[i] = self._mod.get_outputs()[0]
                if i in (1, 3):
                    # real copies: what get_params returns still aliases
                    # the buffers that the next step donates
                    win.snap_params[i] = tuple(
                        {k: jnp.copy(v._data) for k, v in d.items()}
                        for d in self._mod.get_params())
                if i >= self._warmup_steps:
                    raise StopIteration
            elif i % self._k == 0:
                if win.trace_dir is not None:
                    if i == TRACE_START_STEP and not win.tracing:
                        # no Python call stacks: they are most of the
                        # trace and slow the host that is being traced
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
            if self._epoch == 1:
                win.steps += 1
            self._i += 1
            return self._batches[i % len(self._batches)]


_REF_STEPS = {}
_TIMED = []     # the Window being timed, for the one compile listener


def _count_compile(event, *_args, **_kwargs):
    for win in _TIMED:
        if win.t0 is not None and win.t1 is None and "compile" in event:
            win.compiles_in_window += 1


jax.monitoring.register_event_duration_secs_listener(_count_compile)


def _leaf_norms(a, b, scale=1.0):
    """name -> ||a - b|| * scale, worked out on the device."""
    fn = jax.jit(lambda x, y: {k: jnp.sqrt(jnp.sum(jnp.square(
        x[k].astype(jnp.float32) - y[k].astype(jnp.float32)))) * scale
        for k in x})
    return {k: float(v) for k, v in jax.device_get(fn(a, b)).items()}


def _loss_rows(probs, label):
    """Every row's cross-entropy from the softmax output of a step."""
    p = np.asarray(probs, dtype=np.float32)
    picked = p[np.arange(p.shape[0]), np.asarray(label).astype(int)]
    return [float(v) for v in -np.log(np.maximum(picked, 1e-30))]


def reference_readings(ref, cfg, w0, aux0, batches, operand=None,
                       rows=None):
    """The plain reference through the first three steps: the numbers of
    ``compare.numbers``. ``operand`` computes it in a lower precision (the
    control); ``rows`` keeps only those rows of the batch (a planted
    fault)."""
    kw = {} if operand is None else {"operand": operand}
    if rows is not None:
        batches = [(d[rows], l[rows]) for d, l in batches]
    key = (ref.__name__, json.dumps(cfg, sort_keys=True), operand)
    if key not in _REF_STEPS:      # one trace a process, however many seeds
        _REF_STEPS[key] = jax.jit(
            lambda a, x, m, d, l: ref.train_step(cfg, a, x, m, d, l, **kw))
    step = _REF_STEPS[key]
    args, aux = w0, aux0
    mom = jax.tree.map(jnp.zeros_like, w0)
    rows, w1 = [], None
    for i in range(3):              # the batches in the iterator's order
        data, label = batches[i % len(batches)]
        step_rows, args, aux, mom = step(args, aux, mom, data, label)
        rows.append([float(v) for v in jax.device_get(step_rows)])
        if i == 0:
            w1 = args
    lr = cfg["optimizer"]["learning_rate"]
    return {"loss_rows": rows,
            "grad1": _leaf_norms(w0, w1, 1.0 / lr),
            "change3": _leaf_norms(args, w0),
            "stats3": _leaf_norms(aux, aux0)}


def program_readings(cfg, win, w0, aux0, batches):
    """The same numbers from what the timed module produced in the
    warm-up's first three steps."""
    args1, _ = win.snap_params[1]
    args3, aux3 = win.snap_params[3]
    lr = cfg["optimizer"]["learning_rate"]
    labels = [np.asarray(jax.device_get(l)) for _d, l in batches]
    return {"loss_rows": [
                _loss_rows(jax.device_get(win.snap_outputs[i]._data),
                           labels[(i - 1) % len(labels)])
                for i in (1, 2, 3)],
            "grad1": _leaf_norms(w0, args1, 1.0 / lr),
            "change3": _leaf_norms(args3, w0),
            "stats3": _leaf_norms(aux3, aux0)}


def memory_peak_bytes(stats):
    """The peak of one chip: what the runtime's allocator had in use at
    its fullest, plus what it holds reserved for the compiled programs'
    temporaries. The v5e's runtime keeps the two apart (the step's 4.65 GB
    of scratch shows only under ``peak_bytes_reserved``), and together
    with the free block they add up to the chip's limit."""
    return int(stats.get("peak_bytes_in_use", 0)) \
        + int(stats.get("peak_bytes_reserved", 0))


def _fail(msg, code=1):
    print("benchmark: " + msg, file=sys.stderr)
    sys.exit(code)


def devices_for(cell, rehearse_cpu):
    """The chips of the cell, or an exit: a cell is measured on the chip,
    and the CPU only where the rehearsal was asked for by name."""
    devs = jax.devices()
    if rehearse_cpu:
        if devs[0].platform != "cpu":
            _fail("--rehearse-cpu needs JAX_PLATFORMS=cpu, found %s" % devs)
    elif devs[0].platform != "tpu":
        _fail("JAX found no TPU (devices: %s). A cell runs on the chip; "
              "--rehearse-cpu asks for a CPU rehearsal by name." % devs, 3)
    if len(devs) < cell["chips"]:
        _fail("the cell needs %d chips, JAX found %d" %
              (cell["chips"], len(devs)), 3)
    return devs[:cell["chips"]]


def build(cell, seed, seconds, trace_dir, devices):
    """Everything up to the ``fit`` call: weights and batches from the
    seed, the module, the iterator. Returns a dict the rest reads."""
    import mxnet_tpu as mx
    from mxnet_tpu.io import DataBatch, DataDesc

    cfg, mix = cell["cfg"], cell["mix"]
    k = cfg["steps_per_dispatch"]
    if k != 1:
        raise ValueError(
            "steps_per_dispatch %r: the comparison with the reference "
            "needs the state after single steps, which only the per-step "
            "program exposes" % (k,))
    if mix["warmup_steps"] < 4 or mix["warmup_steps"] % k:
        raise ValueError("warmup_steps must be a multiple of "
                         "steps_per_dispatch and at least 4")
    ref = importlib.import_module("references." + cfg["reference"])

    dp = rep = None
    if len(devices) > 1:
        mesh = Mesh(devices, ("dp",))
        dp, rep = NamedSharding(mesh, P("dp")), NamedSharding(mesh, P())
    with jax.default_device(devices[0]):
        w0, aux0 = ref.init_params(cfg, seed, sharding=rep)
        batches = traffic.make_batches(mix, cfg, seed, sharding=dp)
        given = jax.tree.map(jnp.copy, (w0, aux0))   # the step donates them

    ctxs = [mx.tpu(d.id) if d.platform == "tpu" else mx.cpu(d.id)
            for d in devices]
    nd = mx.nd.NDArray
    host = mix["placement"] == "host"
    feed = [DataBatch(
        data=[mx.nd.array(d, ctx=mx.cpu()) if host else nd(d, ctx=ctxs[0])],
        label=[mx.nd.array(l, ctx=mx.cpu()) if host else nd(l, ctx=ctxs[0])])
        for d, l in batches]
    descs = ([DataDesc("data", tuple(batches[0][0].shape))],
             [DataDesc("softmax_label", tuple(batches[0][1].shape))])

    builder_mod, builder_fn = cfg["symbol"]["builder"].rsplit(".", 1)
    sym = getattr(importlib.import_module(builder_mod), builder_fn)(
        **cfg["symbol"]["kwargs"])
    want = set(sym.list_arguments()) - {"data", "softmax_label"}
    if want != set(w0) or set(sym.list_auxiliary_states()) != set(aux0):
        raise ValueError("the symbol's variables are not the reference's: "
                         "%s" % sorted(want ^ set(w0))[:6])
    mod = mx.mod.Module(sym, context=ctxs if len(ctxs) > 1 else ctxs[0])
    win = Window(seconds, trace_dir)
    it = WindowIter(feed, descs, mix["warmup_steps"], k, win, mod)
    return {"mx": mx, "mod": mod, "it": it, "win": win, "ref": ref,
            "w0": w0, "aux0": aux0, "given": given, "batches": batches,
            "ctxs": ctxs}


def fit(cell, b):
    """The one ``fit`` call: warm-up epoch, stamp, window epoch, stamp."""
    mx, mod, win = b["mx"], b["mod"], b["win"]
    cfg = cell["cfg"]
    probe = min(b["w0"], key=lambda n: b["w0"][n].size)

    def epoch_end(epoch, _symbol, arg_params, _aux_params):
        # a host fetch of a parameter of this epoch's last step returns
        # only after every dispatched step has run
        with jax.profiler.TraceAnnotation("bench/final_fetch"):
            arg_params[probe].asnumpy()
        now = time.perf_counter()
        if epoch == 0:
            win.t0 = now
        else:
            win.t1 = now

    opt = dict(cfg["optimizer"])
    name = opt.pop("name")
    nd = mx.nd.NDArray
    args, aux = b.pop("given")
    _TIMED.append(win)
    try:
        mod.fit(b["it"], num_epoch=2, eval_metric=None,
                kvstore=cfg["kvstore"], optimizer=name, optimizer_params=opt,
                arg_params={k: nd(v, ctx=b["ctxs"][0])
                            for k, v in args.items()},
                aux_params={k: nd(v, ctx=b["ctxs"][0])
                            for k, v in aux.items()},
                steps_per_dispatch=cfg["steps_per_dispatch"],
                epoch_end_callback=epoch_end)
    finally:
        _TIMED.remove(win)
    if win.tracing:                     # the window ended inside the trace
        jax.profiler.stop_trace()
        win.tracing = False
    if getattr(mod, "_fused", None) is None:
        _fail("the fused train step did not engage: the cell would time "
              "the eager path")
    if win.compiles_in_window:
        _fail("%d compilations inside the measured window"
              % win.compiles_in_window)


def drive(cell, seed, seconds, trace_dir, devices):
    """Build, fit, read what the program produced, free its state. Returns
    what the comparison and the result need, with no module left alive."""
    b = build(cell, seed, seconds, trace_dir, devices)
    fit(cell, b)
    win = b["win"]
    stats = [d.memory_stats() or {} for d in devices]
    batches = [(jnp.asarray(d), jnp.asarray(l)) for d, l in b["batches"]]
    out = {"win": win, "ref": b["ref"], "w0": b["w0"], "aux0": b["aux0"],
           "batches": batches,
           "memory_peak": max(memory_peak_bytes(s) for s in stats),
           "memory_stats": stats[0],
           "prog": program_readings(cell["cfg"], win, b["w0"], b["aux0"],
                                    batches)}
    # free the program's state before the reference takes the chip
    b.clear()
    win.snap_params.clear()
    win.snap_outputs.clear()
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
    img_per_s = win.steps * cfg["batch_size"] / window_s

    t_ref = time.perf_counter()
    ref = reference_readings(d["ref"], cfg, d["w0"], d["aux0"], d["batches"])
    ref_s = time.perf_counter() - t_ref
    nums = compare.numbers(d["prog"], ref)
    correct, rows = compare.judge(nums, cfg["limits"])

    metrics, breakdown, device_extra = {}, None, {}
    if args.trace:
        trace = xplane.load(trace_dir)
        if not os.environ.get("BENCH_KEEP_TRACE"):   # for a look by hand
            shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = {"trace": trace, "step_program": cfg["step_program"],
               "steps_per_program": cfg["steps_per_dispatch"],
               "batch_size": cfg["batch_size"], "chips": cell["chips"],
               "train_flops_per_image": flops.train_flops_per_image(cfg),
               "peaks": None if args.rehearse_cpu
               else peaks.peaks(devices[0].device_kind),
               "counters": {"window_steps": win.steps}}
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
    else:
        values = {"train_img_per_s": img_per_s, "setup_s": setup_s}
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
                        "reference_s": ref_s,
                        "memory_stats": d["memory_stats"]}
    result["compared"] = {name: {"value": value, "limit": limit}
                          for name, value, limit in rows}
    for name, (value, detail) in sorted(nums.items()):
        limit = cfg["limits"].get(name)
        print("compared %-12s %.6g  limit %s  (%s)" % (
            name, value, "none" if limit is None else "%.6g" % limit,
            detail), file=sys.stderr)
    return result
