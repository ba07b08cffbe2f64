"""mxlint unit tests: every rule catches a seeded bug and passes on the
corrected version; the baseline ratchet only tightens; the CLI exit codes
hold. All chip-free — Layer 1 never imports jax, Layer 2 lowers under the
CPU platform the suite already pins."""
import json
import os
import sys

import pytest

from mxnet_tpu.analysis import baseline as baseline_mod
from mxnet_tpu.analysis import lint_sources
from mxnet_tpu.analysis import hlo_passes
from mxnet_tpu.analysis.runner import lint_paths

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "tools"))
import mxlint as mxlint_cli  # noqa: E402

sys.path.pop(0)


def _rules(src, path="fix.py"):
    return sorted({d.rule for d in lint_sources({path: src})})


def _diags(src, path="fix.py"):
    return lint_sources({path: src})


# ---------------------------------------------------------------- layer 1

class TestHostSyncRules:
    def test_asnumpy_in_jitted_body_fires(self):
        bad = (
            "import jax\n"
            "def step(params, batch):\n"
            "    h = batch.asnumpy()\n"
            "    return params\n"
            "train = jax.jit(step)\n")
        assert "MXL101" in _rules(bad)

    def test_device_get_in_scanned_body_fires(self):
        bad = (
            "import jax\n"
            "from jax import lax\n"
            "def body(carry, x):\n"
            "    v = jax.device_get(x)\n"
            "    return carry, v\n"
            "def run(xs):\n"
            "    return lax.scan(body, 0, xs)\n")
        assert "MXL101" in _rules(bad)

    def test_np_asarray_in_fused_decorated_fires(self):
        bad = (
            "import numpy as np\n"
            "def fused(f):\n"
            "    return f\n"
            "@fused\n"
            "def step(x):\n"
            "    return np.asarray(x)\n")
        assert "MXL101" in _rules(bad)

    def test_float_coercion_fires_and_corrected_passes(self):
        bad = (
            "import jax\n"
            "def step(x):\n"
            "    return float(x) * 2\n"
            "f = jax.jit(step)\n")
        good = (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "def step(x):\n"
            "    return x.astype(jnp.float32) * 2\n"
            "f = jax.jit(step)\n")
        assert "MXL102" in _rules(bad)
        assert _rules(good) == []

    def test_asnumpy_outside_traced_body_is_fine(self):
        good = (
            "def evaluate(out):\n"
            "    return out.asnumpy().sum()\n")
        assert _rules(good) == []

    def test_unbatched_loop_fetch_fires_and_batched_passes(self):
        bad = (
            "import jax\n"
            "def loop(batches, f):\n"
            "    for b in batches:\n"
            "        out = f(b)\n"
            "        x = out[0].asnumpy()\n"
            "        y = out[1].asnumpy()\n")
        good = (
            "import jax\n"
            "def loop(batches, f):\n"
            "    for b in batches:\n"
            "        out = f(b)\n"
            "        x, y = jax.device_get((out[0], out[1]))\n")
        assert "MXL103" in _rules(bad)
        assert _rules(good) == []


class TestRetraceRules:
    def test_python_branch_on_traced_fires(self):
        bad = (
            "import jax\n"
            "def step(x):\n"
            "    if x > 0:\n"
            "        return x\n"
            "    return -x\n"
            "f = jax.jit(step)\n")
        assert "MXL201" in _rules(bad)

    def test_branch_on_tainted_local_fires(self):
        bad = (
            "import jax\n"
            "def step(batch):\n"
            "    x = batch['data'] * 2\n"
            "    if x.sum() > 0:\n"
            "        x = -x\n"
            "    return x\n"
            "f = jax.jit(step)\n")
        assert "MXL201" in _rules(bad)

    def test_branch_on_shape_or_none_passes(self):
        good = (
            "import jax\n"
            "def step(x, state):\n"
            "    if x.shape[0] > 4:\n"
            "        x = x[:4]\n"
            "    if state is not None and x.ndim == 2:\n"
            "        x = x + state\n"
            "    return x\n"
            "f = jax.jit(step)\n")
        assert _rules(good) == []

    def test_branch_on_dict_key_comprehension_passes(self):
        # dict keys are static pytree structure under jit — the fused
        # Module's per-group downcast filter must stay clean
        good = (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "def step(d):\n"
            "    cast = [k for k, v in d.items()\n"
            "            if v.dtype == jnp.float32 and v.size > 0]\n"
            "    if cast:\n"
            "        pass\n"
            "    return d\n"
            "f = jax.jit(step)\n")
        assert _rules(good) == []

    def test_branch_on_traced_dict_value_in_comprehension_fires(self):
        bad = (
            "import jax\n"
            "def step(d):\n"
            "    pos = [v for k, v in d.items() if v > 0]\n"
            "    return d\n"
            "f = jax.jit(step)\n")
        assert "MXL201" not in _rules(bad)  # comprehension itself is fine
        bad2 = (
            "import jax\n"
            "def step(d):\n"
            "    total = sum(v.sum() for k, v in d.items())\n"
            "    if total > 0:\n"
            "        pass\n"
            "    return d\n"
            "f = jax.jit(step)\n")
        assert "MXL201" in _rules(bad2)

    def test_fstring_of_traced_value_fires_and_shape_passes(self):
        bad = (
            "import jax\n"
            "def step(x):\n"
            "    name = f'val={x}'\n"
            "    return x\n"
            "f = jax.jit(step)\n")
        good = (
            "import jax\n"
            "def step(x):\n"
            "    name = f'shape={x.shape}'\n"
            "    return x\n"
            "f = jax.jit(step)\n")
        assert "MXL202" in _rules(bad)
        assert _rules(good) == []

    def test_unhashable_static_arg_fires_and_tuple_passes(self):
        bad = (
            "import jax\n"
            "def step(x, dims):\n"
            "    return x\n"
            "f = jax.jit(step, static_argnums=(1,))\n"
            "def run(x):\n"
            "    return f(x, [1, 2])\n")
        good = bad.replace("[1, 2]", "(1, 2)")
        assert "MXL203" in _rules(bad)
        assert _rules(good) == []

    def test_unhashable_static_argname_fires(self):
        bad = (
            "import jax\n"
            "def step(x, dims=None):\n"
            "    return x\n"
            "f = jax.jit(step, static_argnames=('dims',))\n"
            "def run(x):\n"
            "    return f(x, dims={'a': 1})\n")
        assert "MXL203" in _rules(bad)


class TestDonationRule:
    BAD = (
        "import jax\n"
        "def step(params, grads):\n"
        "    return params\n"
        "train = jax.jit(step, donate_argnums=(0,))\n"
        "def loop(params, grads):\n"
        "    out = train(params, grads)\n"
        "    norm = params.sum()\n"      # use-after-donation
        "    return out, norm\n")
    GOOD = (
        "import jax\n"
        "def step(params, grads):\n"
        "    return params\n"
        "train = jax.jit(step, donate_argnums=(0,))\n"
        "def loop(params, grads):\n"
        "    params = train(params, grads)\n"   # rebind: buffer is new
        "    norm = params.sum()\n"
        "    return params, norm\n")

    def test_use_after_donation_fires(self):
        assert "MXL301" in _rules(self.BAD)

    def test_rebind_after_donation_passes(self):
        assert _rules(self.GOOD) == []

    def test_method_style_wrapper_tracked(self):
        bad = (
            "import jax\n"
            "class T:\n"
            "    def __init__(self, step):\n"
            "        self._jitted = jax.jit(step, donate_argnums=(0,))\n"
            "    def run(self, params, batch):\n"
            "        out = self._jitted(params, batch)\n"
            "        stale = params\n"
            "        return out, stale\n")
        assert "MXL301" in _rules(bad)


class TestLockRules:
    def test_blocking_queue_put_under_lock_fires(self):
        bad = (
            "import threading, queue\n"
            "_lock = threading.Lock()\n"
            "_q = queue.Queue()\n"
            "def produce(x):\n"
            "    with _lock:\n"
            "        _q.put(x)\n")
        assert "MXL401" in _rules(bad)

    def test_device_get_under_lock_fires_and_outside_passes(self):
        bad = (
            "import jax, threading\n"
            "class Cache:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "    def fetch(self, arr):\n"
            "        with self._lock:\n"
            "            return jax.device_get(arr)\n")
        good = (
            "import jax, threading\n"
            "class Cache:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "    def fetch(self, arr):\n"
            "        host = jax.device_get(arr)\n"
            "        with self._lock:\n"
            "            self.last = host\n"
            "        return host\n")
        assert "MXL401" in _rules(bad)
        assert _rules(good) == []

    def test_condition_wait_is_not_blocking(self):
        # Condition.wait releases the lock while sleeping — the
        # admission-queue pattern must stay clean
        good = (
            "import threading\n"
            "class Q:\n"
            "    def __init__(self):\n"
            "        self._cond = threading.Condition()\n"
            "    def take(self):\n"
            "        with self._cond:\n"
            "            while not self.items:\n"
            "                self._cond.wait(0.1)\n"
            "            return self.items.pop()\n")
        assert _rules(good) == []

    def test_nonblocking_put_passes(self):
        good = (
            "import threading, queue\n"
            "_lock = threading.Lock()\n"
            "_q = queue.Queue()\n"
            "def produce(x):\n"
            "    with _lock:\n"
            "        _q.put(x, block=False)\n")
        assert _rules(good) == []

    def test_inconsistent_lock_order_across_files_fires(self):
        a = (
            "import threading\n"
            "a_lock = threading.Lock()\n"
            "b_lock = threading.Lock()\n"
            "def f():\n"
            "    with a_lock:\n"
            "        with b_lock:\n"
            "            pass\n")
        b = (
            "from mod_a import a_lock, b_lock\n"
            "def g():\n"
            "    with b_lock:\n"
            "        with a_lock:\n"
            "            pass\n")
        diags = lint_sources({"mod_a.py": a, "mod_b.py": b})
        assert {d.rule for d in diags} == {"MXL402"}
        assert {d.path for d in diags} == {"mod_a.py", "mod_b.py"}

    def test_consistent_lock_order_passes(self):
        a = (
            "import threading\n"
            "a_lock = threading.Lock()\n"
            "b_lock = threading.Lock()\n"
            "def f():\n"
            "    with a_lock:\n"
            "        with b_lock:\n"
            "            pass\n"
            "def g():\n"
            "    with a_lock:\n"
            "        with b_lock:\n"
            "            pass\n")
        assert _rules(a) == []


class TestTelemetryDiscipline:
    def test_raw_profiler_counter_fires(self):
        bad = (
            "from mxnet_tpu import profiler\n"
            "def publish(depth):\n"
            "    profiler.record_counter('serve/queue_depth', depth)\n")
        assert "MXL506" in _rules(bad)

    def test_registry_path_and_slash_free_names_pass(self):
        # the registry's own trace mirror is the sanctioned caller, and
        # slash-free names are not registry-owned series
        mirror = (
            "from mxnet_tpu import profiler\n"
            "def _mirror_to_trace(name, value):\n"
            "    profiler.record_counter(name, value)\n")
        assert "MXL506" not in _rules(
            mirror, path="mxnet_tpu/telemetry/registry.py")
        plain = (
            "from mxnet_tpu import profiler\n"
            "def publish(n):\n"
            "    profiler.record_counter('lintdebt', n)\n")
        assert "MXL506" not in _rules(plain)

    def test_registry_publish_passes(self):
        good = (
            "from mxnet_tpu import telemetry\n"
            "def publish(depth):\n"
            "    telemetry.gauge('serve/queue_depth').set(depth)\n")
        assert _rules(good) == []


# ---------------------------------------------------------------- layer 3

class TestUnguardedSharedWrite:
    """MXL601: attribute shared across thread contexts, mixed lock
    discipline."""

    BAD = (
        "import threading\n"
        "class Box:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self.pending = []\n"
        "        self._t = threading.Thread(target=self._loop)\n"
        "        self._t.start()\n"
        "    def _loop(self):\n"
        "        while True:\n"
        "            with self._lock:\n"
        "                self.pending.append(1)\n"
        "    def drain(self):\n"
        "        out = list(self.pending)\n"
        "        self.pending = []\n"
        "        return out\n")

    def test_unlocked_caller_access_fires(self):
        diags = [d for d in _diags(self.BAD) if d.rule == "MXL601"]
        assert len(diags) == 1
        assert diags[0].symbol == "Box.pending"

    def test_locked_everywhere_passes(self):
        good = self.BAD.replace(
            "    def drain(self):\n"
            "        out = list(self.pending)\n"
            "        self.pending = []\n"
            "        return out\n",
            "    def drain(self):\n"
            "        with self._lock:\n"
            "            out = list(self.pending)\n"
            "            self.pending = []\n"
            "        return out\n")
        assert "MXL601" not in _rules(good)

    def test_single_owner_convention_passes(self):
        # never-locked loop state driven from one thread: not a race
        src = (
            "import threading\n"
            "class Loop:\n"
            "    def __init__(self):\n"
            "        self.steps = 0\n"
            "        self._t = threading.Thread(target=self.run_loop)\n"
            "    def run_loop(self):\n"
            "        self.steps += 1\n")
        assert "MXL601" not in _rules(src)


class TestBlockingUnderFleetLock:
    """MXL602: fsync / journal append / socket / sleep inside a
    critical section."""

    def test_fsync_under_lock_fires(self):
        bad = (
            "import os, threading\n"
            "class Journal:\n"
            "    def __init__(self, fh):\n"
            "        self._lock = threading.Lock()\n"
            "        self._fh = fh\n"
            "    def append(self, rec):\n"
            "        with self._lock:\n"
            "            self._fh.write(rec)\n"
            "            os.fsync(self._fh.fileno())\n")
        assert "MXL602" in _rules(bad)

    def test_fsync_outside_lock_passes(self):
        good = (
            "import os, threading\n"
            "class Journal:\n"
            "    def __init__(self, fh):\n"
            "        self._lock = threading.Lock()\n"
            "        self._fh = fh\n"
            "    def append(self, rec):\n"
            "        with self._lock:\n"
            "            self._fh.write(rec)\n"
            "        os.fsync(self._fh.fileno())\n")
        assert "MXL602" not in _rules(good)

    def test_journal_append_under_lock_fires(self):
        bad = (
            "class Router:\n"
            "    def set_split(self, model, split):\n"
            "        with self._lock:\n"
            "            self._journal_append('split', {'m': model})\n"
            "            self.table = split\n")
        assert "MXL602" in _rules(bad)

    def test_set_split_pattern_passes(self):
        # journal first (outside the lock), then mutate under it
        good = (
            "class Router:\n"
            "    def set_split(self, model, split):\n"
            "        self._journal_append('split', {'m': model})\n"
            "        with self._lock:\n"
            "            self.table = split\n")
        assert "MXL602" not in _rules(good)

    def test_sleep_under_lock_fires(self):
        bad = (
            "import threading, time\n"
            "_lock = threading.Lock()\n"
            "def poke():\n"
            "    with _lock:\n"
            "        time.sleep(0.1)\n")
        assert "MXL602" in _rules(bad)


class TestWallClockLiveness:
    """MXL603: time.time() feeding liveness/lease/backoff deadlines."""

    def test_wall_clock_deadline_fires(self):
        bad = (
            "import time\n"
            "def lease():\n"
            "    deadline = time.time() + 5.0\n"
            "    return deadline\n")
        assert "MXL603" in _rules(bad)

    def test_monotonic_deadline_passes(self):
        good = (
            "import time\n"
            "def lease():\n"
            "    deadline = time.monotonic() + 5.0\n"
            "    return deadline\n")
        assert "MXL603" not in _rules(good)

    def test_wall_clock_lease_compare_fires(self):
        bad = (
            "import time\n"
            "class Registry:\n"
            "    def check(self, rec):\n"
            "        return time.time() < rec.lease_expiry\n")
        assert "MXL603" in _rules(bad)

    def test_wall_clock_in_liveness_fn_fires(self):
        bad = (
            "import time\n"
            "def sweep_dead(registry):\n"
            "    now = time.time()\n"
            "    return [r for r in registry if r.t < now]\n")
        assert "MXL603" in _rules(bad)

    def test_wall_clock_log_stamp_passes(self):
        # wall clock is fine for log timestamps
        good = (
            "import time\n"
            "def log_stamp():\n"
            "    return time.time()\n")
        assert "MXL603" not in _rules(good)


class TestJournalFirst:
    """MXL604: control-route mutations must journal first, required."""

    HANDLER = (
        "class Handler:\n"
        "    def do_POST(self):\n"
        "        payload = self._read_json()\n"
        "        if self.path.startswith('/fleet/split'):\n"
        "            self.router.set_split(payload['m'], payload['s'])\n")

    def test_mutate_before_append_fires(self):
        bad = (
            "class Router:\n"
            "    def _journal_append(self, kind, rec, required=False):\n"
            "        self._journal.append((kind, rec))\n"
            "    def set_split(self, model, split):\n"
            "        self.splits[model] = split\n"
            "        self._journal_append('split', {'m': model},\n"
            "                             required=True)\n"
            + self.HANDLER)
        diags = [d for d in _diags(bad) if d.rule == "MXL604"]
        assert diags and "mutated before" in diags[0].message

    def test_append_without_required_fires(self):
        bad = (
            "class Router:\n"
            "    def _journal_append(self, kind, rec, required=False):\n"
            "        self._journal.append((kind, rec))\n"
            "    def set_split(self, model, split):\n"
            "        self._journal_append('split', {'m': model})\n"
            "        self.splits[model] = split\n"
            + self.HANDLER)
        diags = [d for d in _diags(bad) if d.rule == "MXL604"]
        assert diags and "required=True" in diags[0].message

    def test_journal_first_required_passes(self):
        good = (
            "class Router:\n"
            "    def _journal_append(self, kind, rec, required=False):\n"
            "        self._journal.append((kind, rec))\n"
            "    def set_split(self, model, split):\n"
            "        self._journal_append('split', {'m': model},\n"
            "                             required=True)\n"
            "        with self._lock:\n"
            "            self.splits[model] = split\n"
            + self.HANDLER)
        assert "MXL604" not in _rules(good)


class TestEpochFencing:
    """MXL605: state-mutating control routes must check the fence."""

    ROUTES = (
        "        if self.path.startswith('/fleet/split'):\n"
        "            self.router.set_split(payload)\n"
        "        elif self.path.startswith('/admin/drain'):\n"
        "            self.router.drain()\n")

    def test_unfenced_routes_fire(self):
        bad = (
            "class Handler:\n"
            "    def do_POST(self):\n"
            "        payload = self._read_json()\n"
            + self.ROUTES)
        diags = [d for d in _diags(bad) if d.rule == "MXL605"]
        assert len(diags) == 2

    def test_preamble_fence_covers_every_route(self):
        good = (
            "class Handler:\n"
            "    def do_POST(self):\n"
            "        payload = self._read_json()\n"
            "        if self.path.startswith(('/fleet/', '/admin/')) \\\n"
            "                and not self._fence(payload):\n"
            "            return\n"
            + self.ROUTES)
        assert "MXL605" not in _rules(good)

    def test_in_branch_fence_passes(self):
        good = (
            "class Handler:\n"
            "    def do_POST(self):\n"
            "        payload = self._read_json()\n"
            "        if self.path.startswith('/fleet/split'):\n"
            "            if not self._fence(payload):\n"
            "                return\n"
            "            self.router.set_split(payload)\n")
        assert "MXL605" not in _rules(good)


class TestPayloadDeterminism:
    """MXL606: journaled/dispatched payloads must replay bitwise."""

    def test_set_and_wall_clock_payload_fires(self):
        bad = (
            "import time\n"
            "class Router:\n"
            "    def record(self, replicas):\n"
            "        rec = {'replicas': {r for r in replicas},\n"
            "               'ts': time.time()}\n"
            "        self._journal_append('epoch', rec, required=True)\n")
        diags = [d for d in _diags(bad) if d.rule == "MXL606"]
        assert len(diags) == 2

    def test_sorted_payload_passes(self):
        good = (
            "class Router:\n"
            "    def record(self, replicas, stamp):\n"
            "        rec = {'replicas': sorted(replicas),\n"
            "               'stamp': stamp}\n"
            "        self._journal_append('epoch', rec, required=True)\n")
        assert "MXL606" not in _rules(good)

    def test_rng_draw_in_dispatch_fires(self):
        bad = (
            "import random\n"
            "def dispatch(rng, payload):\n"
            "    dispatch_payload({'jitter': rng.uniform(0, 1)})\n")
        assert "MXL606" in _rules(bad)


def test_parse_error_is_a_diagnostic_not_a_crash():
    diags = _diags("def broken(:\n")
    assert [d.rule for d in diags] == ["MXL001"]


# ------------------------------------------------------------ diagnostics

def test_baseline_key_is_line_number_free():
    """Inserting code above a violation must not churn its baseline key."""
    bad = (
        "import jax\n"
        "def step(x):\n"
        "    return float(x)\n"
        "f = jax.jit(step)\n")
    shifted = "import os\n\n\n" + bad
    k1 = [d.key() for d in _diags(bad)]
    k2 = [d.key() for d in _diags(shifted)]
    assert k1 == k2 and len(k1) == 1
    assert "::step#0" in k1[0]


def test_diagnostic_payload_fields():
    d = _diags("import jax\n"
               "def step(x):\n"
               "    return float(x)\n"
               "f = jax.jit(step)\n")[0]
    payload = d.to_dict()
    for field in ("rule", "path", "line", "col", "severity", "symbol",
                  "message", "hint", "key"):
        assert field in payload
    assert payload["line"] == 3
    assert "float" in d.format()


# ---------------------------------------------------------------- layer 2

@pytest.fixture(scope="module")
def lowerings():
    import jax
    import jax.numpy as jnp
    import numpy as np

    w = np.zeros((256, 256), np.float32)
    g = np.zeros((256, 256), np.float32)

    def sgd(w, g):
        # two outputs so BOTH donated inputs have a buffer to alias
        return w - 0.1 * g, g * 0.9

    def sgd_bf16_detour(w, g):
        return (w - (0.1 * g.astype(jnp.bfloat16)).astype(jnp.float32),
                g * 0.9)

    def with_callback(w, g):
        jax.debug.callback(lambda v: None, g.sum())
        return w - 0.1 * g, g * 0.9

    return {
        "donated": jax.jit(sgd, donate_argnums=(0, 1)).lower(w, g).as_text(),
        "undonated": jax.jit(sgd).lower(w, g).as_text(),
        "bf16_detour": jax.jit(sgd_bf16_detour).lower(w, g).as_text(),
        "callback": jax.jit(with_callback).lower(w, g).as_text(),
    }


class TestHloPasses:
    def test_convert_budget_catches_and_passes(self, lowerings):
        bad = hlo_passes.convert_budget_pass(
            lowerings["bf16_detour"], "step", budget=0)
        assert len(bad) == 1 and bad[0].rule == "MXL501"
        assert hlo_passes.convert_budget_pass(
            lowerings["donated"], "step", budget=0) == []

    def test_donation_coverage_catches_and_passes(self, lowerings):
        bad = hlo_passes.donation_coverage_pass(
            lowerings["undonated"], "step", min_coverage=0.5,
            large_bytes=1024)
        assert len(bad) == 1 and bad[0].rule == "MXL502"
        assert hlo_passes.donation_coverage_pass(
            lowerings["donated"], "step", min_coverage=0.99,
            large_bytes=1024) == []

    def test_donation_coverage_no_large_params_is_clean(self):
        # zero large params -> nothing worth donating -> coverage 1.0
        assert hlo_passes.donation_coverage("", large_bytes=1)[2] == 1.0

    def test_d2h_catches_callback_and_passes_clean(self, lowerings):
        bad = hlo_passes.d2h_transfer_pass(
            lowerings["callback"], "step", budget=0)
        assert len(bad) == 1 and bad[0].rule == "MXL503"
        assert hlo_passes.d2h_transfer_pass(
            lowerings["donated"], "step", budget=0) == []

    def test_fusion_bytes_catches_and_passes(self, lowerings):
        # the sgd program writes a few elementwise results (256x256 f32
        # each): a zero budget must flag it, a generous one must not
        bad = hlo_passes.fusion_bytes_pass(
            lowerings["donated"], "step", budget_gib=0.0)
        assert len(bad) == 1 and bad[0].rule == "MXL505"
        assert "GiB" in bad[0].message
        assert hlo_passes.fusion_bytes_pass(
            lowerings["donated"], "step", budget_gib=64.0) == []

    # MXL507 fixtures: hand-written StableHLO with known dataflow. The
    # chained module reduces THROUGH the only compute chain (dot ->
    # all_reduce -> dot): nothing can overlap. The overlapped module has
    # an independent dot the scheduler can slide under the collective.
    _DDP_BAD = (
        'func.func public @main(%arg0: tensor<4x4xf32>) {\n'
        '  %0 = stablehlo.dot_general %arg0, %arg0 : tensor<4x4xf32>\n'
        '  %1 = "stablehlo.all_reduce"(%0) <{replica_groups = '
        'dense<[[0,1]]>}> ({\n'
        '  ^bb0(%arg1: tensor<f32>, %arg2: tensor<f32>):\n'
        '    %4 = stablehlo.add %arg1, %arg2 : tensor<f32>\n'
        '    stablehlo.return %4 : tensor<f32>\n'
        '  }) : tensor<4x4xf32>\n'
        '  %2 = stablehlo.dot_general %1, %1 : tensor<4x4xf32>\n'
        '  return %2 : tensor<4x4xf32>\n'
        '}\n')
    _DDP_GOOD = (
        'func.func public @main(%arg0: tensor<4x4xf32>) {\n'
        '  %0 = stablehlo.dot_general %arg0, %arg0 : tensor<4x4xf32>\n'
        '  %1 = "stablehlo.all_reduce"(%0) <{replica_groups = '
        'dense<[[0,1]]>}> ({\n'
        '  ^bb0(%arg1: tensor<f32>, %arg2: tensor<f32>):\n'
        '    %4 = stablehlo.add %arg1, %arg2 : tensor<f32>\n'
        '    stablehlo.return %4 : tensor<f32>\n'
        '  }) : tensor<4x4xf32>\n'
        '  %2 = stablehlo.dot_general %arg0, %arg0 : tensor<4x4xf32>\n'
        '  %3 = stablehlo.add %1, %2 : tensor<4x4xf32>\n'
        '  return %3 : tensor<4x4xf32>\n'
        '}\n')

    def test_collective_interleave_catches_and_passes(self):
        bad = hlo_passes.collective_interleave_pass(
            self._DDP_BAD, "ddp/step", max_collectives=1)
        assert len(bad) == 1 and bad[0].rule == "MXL507"
        assert "critical path" in bad[0].message
        assert hlo_passes.collective_interleave_pass(
            self._DDP_GOOD, "ddp/step", max_collectives=1) == []

    def test_collective_interleave_budget_and_absence(self):
        over = hlo_passes.collective_interleave_pass(
            self._DDP_GOOD, "ddp/step", max_collectives=0)
        assert len(over) == 1 and "bucket plan" in over[0].message
        none = hlo_passes.collective_interleave_pass(
            "func.func public @main() {\n  return\n}\n", "ddp/step")
        assert len(none) == 1 and "not being reduced" in none[0].message

    def test_decode_cache_discipline_catches_and_passes(self, lowerings):
        # donated in-place update over the "cache" params: clean
        assert hlo_passes.decode_cache_discipline_pass(
            lowerings["donated"], "decode", cache_params=(0, 1)) == []
        # same program without donation: the KV buffers round-trip
        bad = hlo_passes.decode_cache_discipline_pass(
            lowerings["undonated"], "decode", cache_params=(0, 1))
        assert len(bad) == 1 and bad[0].rule == "MXL508"
        assert "not donated" in bad[0].message
        # host callback inside the step: a d2h per token
        leak = hlo_passes.decode_cache_discipline_pass(
            lowerings["callback"], "decode", cache_params=())
        assert len(leak) == 1 and leak[0].rule == "MXL508"
        assert "host-transfer" in leak[0].message

    def test_speculative_dispatch_catches_and_passes(self, lowerings):
        # MXL510 fixture pair rides the same programs as MXL508: what
        # changes is the contract — ALL cache params (verifier + draft
        # pairs) donated, zero host transfers in the FUSED program.
        # fused + donated: clean
        assert hlo_passes.speculative_dispatch_pass(
            lowerings["donated"], "draft_verify",
            cache_params=(0, 1)) == []
        # undonated draft/verifier KV: the page stores copy every window
        bad = hlo_passes.speculative_dispatch_pass(
            lowerings["undonated"], "draft_verify", cache_params=(0, 1))
        assert len(bad) == 1 and bad[0].rule == "MXL510"
        assert "not donated" in bad[0].message
        # a host callback inside the step: the tell of a draft
        # dispatched separately from its verifier (extra d2h per window)
        leak = hlo_passes.speculative_dispatch_pass(
            lowerings["callback"], "draft_verify", cache_params=())
        assert len(leak) == 1 and leak[0].rule == "MXL510"
        assert "not fused with its verifier" in leak[0].message

    def test_embedding_lookup_discipline_catches_and_passes(
            self, lowerings):
        # MXL511 fixture pair rides the same programs as MXL508: the
        # "cache" param here plays the hot-row embedding buffer the
        # RecommendEngine donates (argnum 0).
        assert hlo_passes.embedding_lookup_discipline_pass(
            lowerings["donated"], "recommend", cache_params=(0, 1)) == []
        # undonated hot-row buffer: the resident rows copy per batch
        bad = hlo_passes.embedding_lookup_discipline_pass(
            lowerings["undonated"], "recommend", cache_params=(0, 1))
        assert len(bad) == 1 and bad[0].rule == "MXL511"
        assert "not donated" in bad[0].message
        # a host callback inside the served lookup: hit/miss accounting
        # must stay host-held (HotRowCache counters), zero extra d2h
        leak = hlo_passes.embedding_lookup_discipline_pass(
            lowerings["callback"], "recommend", cache_params=())
        assert len(leak) == 1 and leak[0].rule == "MXL511"
        assert "host-transfer" in leak[0].message

    # MXL512 fixtures: hand-written StableHLO around the pass's tell.
    # BAD materializes the (seq, ctx) score softmax — an exponential
    # whose f32 result spans the full context width in its last dim.
    # GOOD is the flash kernel's footprint: exps over kernel tiles
    # (last dim < ctx) plus the sampler's log-of-uniform Gumbel trick,
    # neither of which may fire the rule.
    _ATTN_BAD = (
        'func.func public @main(%arg0: tensor<8x4x48xf32>) {\n'
        '  %0 = stablehlo.exponential %arg0 : tensor<8x4x48xf32>\n'
        '  %1 = stablehlo.exponential %arg0 : tensor<8x4x48xf32>\n'
        '  return %1 : tensor<8x4x48xf32>\n'
        '}\n')
    _ATTN_GOOD = (
        'func.func public @main(%arg0: tensor<16x16xf32>, '
        '%arg1: tensor<8x4xf32>) {\n'
        '  %0 = stablehlo.exponential %arg0 : tensor<16x16xf32>\n'
        '  %1 = stablehlo.log %arg1 : tensor<8x4xf32>\n'
        '  return %0 : tensor<16x16xf32>\n'
        '}\n')

    def test_attention_fusion_catches_and_passes(self):
        # decode geometry: ctx = page_size * max_pages_per_slot = 48
        bad = hlo_passes.attention_fusion_pass(
            self._ATTN_BAD, "decode_step", ctx=48)
        assert len(bad) == 1 and bad[0].rule == "MXL512"
        assert "softmax exponential" in bad[0].message
        assert "8x4x48xf32" in bad[0].message
        # tile-width exps (16 < 48) and the Gumbel log: clean
        assert hlo_passes.attention_fusion_pass(
            self._ATTN_GOOD, "decode_step", ctx=48) == []
        # the same tile exp IS the score block when ctx shrinks to it
        tight = hlo_passes.attention_fusion_pass(
            self._ATTN_GOOD, "decode_step", ctx=16)
        assert len(tight) == 1 and tight[0].rule == "MXL512"

    def test_attention_fusion_holds_sync_budget(self, lowerings):
        # a host callback inside the step: fusing attention must not
        # add device syncs (the MXL508 one-fetch contract still holds)
        leak = hlo_passes.attention_fusion_pass(
            lowerings["callback"], "decode_step", ctx=48)
        assert len(leak) == 1 and leak[0].rule == "MXL512"
        assert "must not add device syncs" in leak[0].message
        assert hlo_passes.attention_fusion_pass(
            lowerings["donated"], "decode_step", ctx=10 ** 6) == []

    # MXL509 fixtures: hand-written StableHLO in the shape the quantized
    # serving ops lower to. GOOD: f32 activations quantize (f32->i8), an
    # int8 dot accumulates in i32, and the only upcast is the i32
    # accumulator entering the dequant epilogue. BAD: the int8 weight is
    # upcast i8->f32 and the dot runs in f32 — the artifact shrank but
    # the compute did not quantize.
    _QUANT_GOOD = (
        'func.func public @main(%arg0: tensor<4x256xf32>) {\n'
        '  %c = stablehlo.constant dense<1> : tensor<8x256xi8>\n'
        '  %0 = stablehlo.convert %arg0 : (tensor<4x256xf32>) -> '
        'tensor<4x256xi8>\n'
        '  %1 = stablehlo.dot_general %0, %c, contracting_dims = [1] x '
        '[1] : (tensor<4x256xi8>, tensor<8x256xi8>) -> tensor<4x8xi32>\n'
        '  %2 = stablehlo.convert %1 : (tensor<4x8xi32>) -> '
        'tensor<4x8xf32>\n'
        '  return %2 : tensor<4x8xf32>\n'
        '}\n')
    _QUANT_BAD = (
        'func.func public @main(%arg0: tensor<4x256xf32>) {\n'
        '  %c = stablehlo.constant dense<1> : tensor<8x256xi8>\n'
        '  %0 = stablehlo.convert %c : (tensor<8x256xi8>) -> '
        'tensor<8x256xf32>\n'
        '  %1 = stablehlo.dot_general %arg0, %0, contracting_dims = [1] '
        'x [1] : (tensor<4x256xf32>, tensor<8x256xf32>) -> '
        'tensor<4x8xf32>\n'
        '  return %1 : tensor<4x8xf32>\n'
        '}\n')

    def test_quant_dequant_budget_catches_and_passes(self):
        assert hlo_passes.quant_dequant_budget_pass(
            self._QUANT_GOOD, "int8/predict", min_int8_ops=1) == []
        bad = hlo_passes.quant_dequant_budget_pass(
            self._QUANT_BAD, "int8/predict", min_int8_ops=1)
        # both failure modes: no int8 compute AND a weight upcast
        assert len(bad) == 2
        assert all(d.rule == "MXL509" for d in bad)
        assert "i8->f32" in bad[1].message

    def test_quant_dequant_upcast_budget_is_a_ratchet(self):
        # a module with valid int8 compute plus ONE stray i8->f32: the
        # budget tolerates it at 1 (MXL501 idiom) and flags it at 0
        mixed = self._QUANT_GOOD.replace(
            '  return %2 : tensor<4x8xf32>\n',
            '  %3 = stablehlo.convert %c : (tensor<8x256xi8>) -> '
            'tensor<8x256xf32>\n'
            '  return %2 : tensor<4x8xf32>\n')
        assert hlo_passes.quant_dequant_budget_pass(
            mixed, "int8/predict", upcast_budget=1) == []
        over = hlo_passes.quant_dequant_budget_pass(
            mixed, "int8/predict", upcast_budget=0)
        assert len(over) == 1 and over[0].rule == "MXL509"

    def test_collective_overlap_report_is_per_func(self):
        # SSA names restart per func.func: a %0 in a second function must
        # not alias the first function's dataflow
        two = self._DDP_BAD + self._DDP_GOOD.replace("@main", "@shmap_body")
        rep = hlo_passes.collective_overlap_report(two)
        assert rep["collectives"] == 2
        assert rep["overlappable"] == 1

    def test_metrics_from_text(self, lowerings):
        m = hlo_passes.metrics_from_text(lowerings["donated"],
                                         large_bytes=1024)
        assert m["donation_coverage"] == 1.0
        assert m["d2h_count"] == 0
        assert m["elementwise_gib"] >= 0.0
        assert m["pallas_kernels"] == 0
        m2 = hlo_passes.metrics_from_text(lowerings["bf16_detour"],
                                          large_bytes=1024)
        assert m2["convert_f32_bf16"] >= 2


class TestRecompileFingerprint:
    def test_shape_churn_flagged(self):
        import numpy as np
        fp = hlo_passes.RecompileFingerprint("predict", max_variants=2)
        for n in (1, 2, 3, 4):
            fp.observe(np.zeros((n, 8), np.float32))
        diags = fp.diagnostics()
        assert len(diags) == 1 and diags[0].rule == "MXL504"
        assert fp.variants == 4

    def test_bucketed_shapes_pass(self):
        import numpy as np
        fp = hlo_passes.RecompileFingerprint("predict", max_variants=2)
        for n in (1, 3, 2, 4):
            bucket = 4    # serve/engine_cache-style padding
            fp.observe(np.zeros((bucket, 8), np.float32))
        assert fp.diagnostics() == [] and fp.variants == 1

    def test_static_value_churn_flagged(self):
        fp = hlo_passes.RecompileFingerprint("step", max_variants=2)
        for lr in (0.1, 0.2, 0.3):
            fp.observe(lr=lr)
        assert fp.diagnostics() and fp.variants == 3


# ------------------------------------------------------------ the ratchet

BAD_SRC = (
    "import jax\n"
    "def step(x):\n"
    "    return float(x)\n"
    "f = jax.jit(step)\n")


class TestBaselineRatchet:
    def _write(self, tmp_path, name, src):
        p = tmp_path / name
        p.write_text(src)
        return str(p)

    def test_new_violation_fails_baselined_passes(self, tmp_path):
        f = self._write(tmp_path, "mod.py", BAD_SRC)
        bl = str(tmp_path / "baseline.json")
        diags = lint_paths([f], root=str(tmp_path))
        assert diags
        # not baselined -> new
        new, baselined, stale = baseline_mod.partition(
            diags, baseline_mod.load(bl))
        assert new and not baselined
        # baselined -> passes
        baseline_mod.update(bl, diags, allow_growth=True)
        new, baselined, stale = baseline_mod.partition(
            diags, baseline_mod.load(bl))
        assert not new and baselined and not stale

    def test_update_shrinks_but_never_grows(self, tmp_path):
        f = self._write(tmp_path, "mod.py", BAD_SRC)
        bl = str(tmp_path / "baseline.json")
        diags = lint_paths([f], root=str(tmp_path))
        baseline_mod.update(bl, diags, allow_growth=True)
        assert len(baseline_mod.load(bl)) == 1

        # violation fixed -> shrink happens without any flag
        self._write(tmp_path, "mod.py",
                    "def step(x):\n    return x\n")
        diags = lint_paths([str(tmp_path / "mod.py")], root=str(tmp_path))
        baseline_mod.update(bl, diags)
        assert baseline_mod.load(bl) == {}

        # new violation -> growth refused without allow_growth
        self._write(tmp_path, "mod.py", BAD_SRC)
        diags = lint_paths([str(tmp_path / "mod.py")], root=str(tmp_path))
        with pytest.raises(baseline_mod.BaselineGrowthError):
            baseline_mod.update(bl, diags)
        assert baseline_mod.load(bl) == {}    # refused update wrote nothing
        baseline_mod.update(bl, diags, allow_growth=True)
        assert len(baseline_mod.load(bl)) == 1

    def test_layer3_growth_refused(self, tmp_path):
        """New MXL6xx findings ride the same one-way ratchet."""
        f = self._write(tmp_path, "mod.py", (
            "import time\n"
            "def lease():\n"
            "    deadline = time.time() + 5.0\n"
            "    return deadline\n"))
        bl = str(tmp_path / "baseline.json")
        baseline_mod.update(bl, [])            # seed an empty baseline
        diags = lint_paths([f], root=str(tmp_path))
        assert {d.rule for d in diags} == {"MXL603"}
        with pytest.raises(baseline_mod.BaselineGrowthError):
            baseline_mod.update(bl, diags)
        assert baseline_mod.load(bl) == {}     # refusal wrote nothing

    def test_unsupported_baseline_format_raises(self, tmp_path):
        bl = tmp_path / "baseline.json"
        bl.write_text(json.dumps({"version": 99, "entries": {}}))
        with pytest.raises(ValueError):
            baseline_mod.load(str(bl))


# ------------------------------------------------------------------- CLI

class TestCli:
    def test_exit_codes_and_json(self, tmp_path, capsys):
        mod = tmp_path / "mod.py"
        mod.write_text(BAD_SRC)
        bl = str(tmp_path / "bl.json")

        rc = mxlint_cli.main([str(mod), "--no-baseline", "--json"])
        out = json.loads(capsys.readouterr().out)
        assert rc == 1 and out["new"] == 1
        assert out["diagnostics"][0]["rule"] == "MXL102"

        # clean file -> 0
        clean = tmp_path / "ok.py"
        clean.write_text("def f(x):\n    return x\n")
        assert mxlint_cli.main([str(clean), "--no-baseline"]) == 0

    def test_rule_filter_and_unknown_rule(self, tmp_path, capsys):
        mod = tmp_path / "mod.py"
        mod.write_text(BAD_SRC)
        rc = mxlint_cli.main([str(mod), "--no-baseline", "--rule",
                              "MXL401"])
        capsys.readouterr()
        assert rc == 0          # only lock rules requested; none fire
        assert mxlint_cli.main(["--rule", "MXL999"]) == 2

    def test_list_rules(self, capsys):
        assert mxlint_cli.main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for rid in ("MXL101", "MXL201", "MXL301", "MXL401", "MXL501",
                    "MXL502", "MXL503", "MXL504"):
            assert rid in out

    def test_baseline_update_guard_needs_full_scope(self, tmp_path,
                                                    capsys):
        rc = mxlint_cli.main(["--baseline-update", "--rule", "MXL101"])
        capsys.readouterr()
        assert rc == 2
        rc = mxlint_cli.main(["--baseline-update", "--concurrency"])
        capsys.readouterr()
        assert rc == 2

    def test_concurrency_scope_filters_layer1(self, tmp_path, capsys):
        mod = tmp_path / "mod.py"
        mod.write_text(BAD_SRC +
                       "import time\n"
                       "def lease():\n"
                       "    deadline = time.time() + 5.0\n"
                       "    return deadline\n")
        rc = mxlint_cli.main([str(mod), "--no-baseline", "--json",
                              "--concurrency"])
        out = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert {d["rule"] for d in out["diagnostics"]} == {"MXL603"}
