"""Kernel-tier policy: who may dispatch to Pallas, and with which config.

The tier is a dispatch *policy* layered over the op registry, not a new
op surface: call-sites in ``ops/nn.py`` and the executor's graph-fusion
pass ask :func:`should_dispatch` per call, and every "no" falls back to
the pure-JAX op — models never see the difference except in speed.

Policy (``MXNET_KERNEL_TIER``):

* ``off``  — never dispatch (the default; tier-1 CI runs here).
* ``safe`` — dispatch only when the tuning cache holds a config for the
  exact (op, shape-bucket, dtype), i.e. someone ran ``tools/autotune.py``
  for this workload.
* ``auto`` — dispatch whenever the eligibility guard passes; tuned config
  if cached, heuristic default otherwise.

Everything here is trace-time: a dict lookup and a couple of counters.
The counters (dispatch / fallback / tuner hit+miss) are what ``bench.py``
emits as the ``kernel_tier`` field.
"""
from __future__ import annotations

import contextlib
import threading

from ..config import flags

__all__ = ["tier", "enabled", "should_dispatch", "resolve_interpret",
           "backend_interprets", "force_compiled", "record_fallback",
           "stats", "reset_stats"]

_VALID = ("off", "safe", "auto")


def tier() -> str:
    """Current policy string; unknown values degrade to 'off'."""
    t = str(flags.kernel_tier).strip().lower()
    return t if t in _VALID else "off"


def enabled() -> bool:
    return tier() != "off"


# --------------------------------------------------------------- interpret
_interpret_override = threading.local()


def resolve_interpret():
    """Pallas interpret= for tier kernels: Mosaic on a TPU backend, the
    interpreter on the CPU backend (tests), an error anywhere else —
    no backend gets a mode picked for it by elimination."""
    forced = getattr(_interpret_override, "value", None)
    if forced is None:
        raw = str(flags.kernel_interpret).strip().lower()
        if raw in ("0", "compiled", "false", "mosaic"):
            forced = False
        elif raw in ("1", "interpret", "true"):
            forced = True
    if forced is not None:
        return bool(forced)
    return backend_interprets()


def backend_interprets():
    """False on the TPU backend, True on the CPU backend, else raises."""
    import jax
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        "Pallas kernels here run compiled on the 'tpu' backend and "
        "interpreted on 'cpu'; the default backend is %r" % backend)


@contextlib.contextmanager
def force_compiled():
    """Force Mosaic (non-interpret) lowering inside the scope — used to
    export TPU-platform HLO from a chip-free host (jax.export with
    platforms=['tpu']); the resulting program is lowered, never run."""
    prev = getattr(_interpret_override, "value", None)
    _interpret_override.value = False
    try:
        yield
    finally:
        _interpret_override.value = prev


# ------------------------------------------------------------------ stats
_lock = threading.Lock()
_stats = {"dispatch": {}, "fallback": {}, "tuner_hits": 0,
          "tuner_misses": 0, "configs": {}}


def reset_stats():
    with _lock:
        _stats["dispatch"].clear()
        _stats["fallback"].clear()
        _stats["configs"].clear()
        _stats["tuner_hits"] = 0
        _stats["tuner_misses"] = 0


def stats():
    """Snapshot of dispatch bookkeeping since the last reset."""
    with _lock:
        return {"tier": tier(),
                "dispatch": dict(_stats["dispatch"]),
                "fallback": dict(_stats["fallback"]),
                "tuner_hits": _stats["tuner_hits"],
                "tuner_misses": _stats["tuner_misses"],
                "configs": dict(_stats["configs"])}


def _record_dispatch(op, cache_key, config, tuned):
    with _lock:
        _stats["dispatch"][op] = _stats["dispatch"].get(op, 0) + 1
        if tuned:
            _stats["tuner_hits"] += 1
        else:
            _stats["tuner_misses"] += 1
        _stats["configs"][cache_key] = dict(config)
    # trace-time census into the run-wide registry (one counter bump per
    # dispatch DECISION, not per execution — this code never runs inside
    # the compiled program)
    from .. import telemetry as _telemetry
    _telemetry.counter("kernel/dispatch_total",
                       "Pallas-tier dispatch decisions").inc(1, op=op)
    _telemetry.counter(
        "kernel/tuner_lookups_total",
        "tuning-cache consults at dispatch").inc(
            1, outcome="hit" if tuned else "miss")


def record_fallback(op, reason):
    """An eligible-looking call-site declined dispatch (guard failure or
    'safe' tier without a tuned entry); bench surfaces the census."""
    with _lock:
        key = "%s: %s" % (op, reason)
        _stats["fallback"][key] = _stats["fallback"].get(key, 0) + 1
    from .. import telemetry as _telemetry
    _telemetry.counter("kernel/fallback_total",
                       "Pallas-tier guard/policy fallbacks").inc(1, op=op)


# --------------------------------------------------------------- dispatch
def should_dispatch(op, shapes, dtype, guard_reason=None):
    """Central tier decision for one call-site.

    ``shapes`` is the op's shape tuple(s) (already guard-checked by the
    caller when ``guard_reason`` is None). Returns ``(go, config)``:
    ``go`` False means fall back to pure JAX; ``config`` is the tuned or
    heuristic kernel config dict when ``go`` is True.
    """
    t = tier()
    if t == "off":
        return False, None
    if guard_reason is not None:
        record_fallback(op, guard_reason)
        return False, None
    from ..tune import cache as _tcache
    cfg, key = _tcache.lookup_config(op, shapes, str(dtype))
    if cfg is None and t == "safe":
        with _lock:
            _stats["tuner_misses"] += 1
        record_fallback(op, "safe tier: no tuned entry for %s" % key)
        return False, None
    tuned = cfg is not None
    if cfg is None:
        from ..tune import space as _tspace
        cfg = _tspace.default_config(op, shapes, str(dtype))
    _record_dispatch(op, key, cfg, tuned)
    return True, cfg
