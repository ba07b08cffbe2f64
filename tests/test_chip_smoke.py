"""chip_smoke.py rehearsed on the CPU, and the rule it stands for: no
path that needs the chip passes without one.

The script itself must refuse to run here; its phase functions run at a
tiny size on the CPU backend (Pallas kernels interpreted), which finds
wrong paths, arguments and control flow before chip time is spent. What
only the chip can show — Mosaic's verdict on each kernel, real widths,
results on the device — is tests/test_tpu_aot_compile.py and the script's
own run there.
"""
import os
import shutil
import subprocess
import sys

import pytest

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402  (imports nothing but the stdlib)

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import models  # noqa: E402
from mxnet_tpu.serve import decode_model as dm  # noqa: E402


def _run(script, cwd, **env):
    return subprocess.run(
        [sys.executable, script], cwd=cwd, capture_output=True, text=True,
        timeout=300, env={**os.environ, "JAX_PLATFORMS": "cpu", **env})


@pytest.mark.parametrize("alone", [False, True])
def test_script_refuses_without_a_chip(tmp_path, alone):
    """Non-zero exit and no result, in the repo and in a directory that
    holds the script and nothing else of the repo."""
    script = os.path.join(ROOT, "chip_smoke.py")
    cwd = ROOT
    if alone:
        script = shutil.copy(script, tmp_path)
        cwd = str(tmp_path)
    r = _run(script, cwd)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


def test_bench_refuses_without_a_chip():
    """bench.py has no CPU fallback either: only BENCH_PLATFORM=cpu, by
    name, gets a CPU smoke run (and never under the device metric's
    name, see bench.py)."""
    r = _run(os.path.join(ROOT, "bench.py"), ROOT, BENCH_PLATFORM="")
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


@pytest.fixture(scope="module")
def trained():
    # the rate goes down with the batch: at batch 8 the real size's 0.05
    # spikes on one init seed in three, whatever the tree
    return chip_smoke.phase_train(ctx=mx.tpu(), num_layers=18, classes=10,
                                  side=32, batch=8, steps_per_epoch=2,
                                  ref_batch=4,
                                  lr=0.005)


def test_train_phase_tiny(trained):
    assert trained["losses"][-1] < trained["losses"][0]


def test_serve_predict_phase_tiny(trained):
    chip_smoke.phase_serve_predict(trained, ctx=mx.tpu(), buckets=(1, 4),
                                   rows=(1, 3, 4))


_TINY_DECODER = dm.DecoderSpec(vocab=97, dim=64, num_heads=4, num_layers=2,
                               max_prompt_len=8, page_size=8,
                               max_pages_per_slot=6, max_slots=4,
                               num_pages=25)
_TINY_PROMPTS = dict(prompt_lens=(3, 8, 20), new_tokens=5)   # 20 > 8: chunked


def test_serve_generate_phase_tiny():
    chip_smoke.phase_serve_generate(_TINY_DECODER, **_TINY_PROMPTS)


@pytest.mark.parametrize("prompt_lens", [(3, 8), (3, 8, 20)],
                         ids=["prefill-window", "chunked"])
def test_kernel_tier_phase_tiny(prompt_lens):
    chip_smoke.phase_kernel_tier(_TINY_DECODER, prompt_lens=prompt_lens,
                                 new_tokens=5)


def test_multichip_phase_tiny():
    """The four-chip phase over this process's virtual CPU devices."""
    n = jax.device_count()
    assert n >= 4, "conftest.py gives the suite 8 virtual devices"
    chip_smoke.phase_multichip(n=n, num_layers=18, classes=10, side=32,
                               batch=2 * n, steps=3, mlp_width=64)


def test_a_failed_check_is_a_nonzero_exit():
    with pytest.raises(SystemExit) as e:
        chip_smoke.check(False, "a phase failed")
    assert e.value.code not in (0, None)


def _bind(contexts):
    sym = models.resnet_symbol(num_classes=10, num_layers=18,
                               image_shape="3,32,32")
    mod = mx.mod.Module(sym, context=contexts)
    mod.bind(data_shapes=[("data", (8, 3, 32, 32))],
             label_shapes=[("softmax_label", (8,))])
    return mod


def test_more_accelerator_contexts_than_devices_raises():
    """Neither wrapped onto the devices there are nor de-duplicated into a
    smaller mesh: an error."""
    n = jax.device_count()
    with pytest.raises(mx.MXNetError, match=r"tpu\(%d\) requested" % n):
        _bind([mx.tpu(i) for i in range(n + 1)])
    with pytest.raises(mx.MXNetError, match="device of its own"):
        _bind([mx.tpu(0), mx.tpu(0)])
    assert _bind([mx.tpu(0), mx.tpu(1)])._exec._mesh.devices.size == 2


def test_accelerator_context_needs_a_cpu_pinned_process(monkeypatch):
    """mx.tpu() maps onto CPU devices only where the process asked for
    the CPU by name; a host that merely has no chip gets an error."""
    from mxnet_tpu import context
    monkeypatch.setattr(context, "cpu_pinned", lambda: False)
    with pytest.raises(mx.MXNetError, match="no accelerator"):
        mx.tpu(0).jax_device
    monkeypatch.undo()
    assert mx.tpu(0).jax_device.platform == "cpu"


def test_unknown_device_kind_has_no_peaks():
    from mxnet_tpu import perfmodel
    assert perfmodel.peak_flops("TPU v5 lite") == 197e12
    for fn in (perfmodel.peak_flops, perfmodel.hbm_bytes_per_s,
               perfmodel.interconnect_bytes_per_s):
        with pytest.raises(KeyError, match="no peak numbers"):
            fn("cpu")
    # on the CPU backend an estimate models the chip, by name
    assert perfmodel.modelled_device_kind() == perfmodel.DEFAULT_DEVICE_KIND


def test_kernels_pick_their_mode_from_the_backend(monkeypatch):
    """Mosaic on 'tpu', the interpreter on 'cpu', an error elsewhere."""
    from mxnet_tpu.kernels import tier
    assert tier.resolve_interpret() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert tier.resolve_interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        tier.resolve_interpret()


def test_native_library_reports_why_it_is_missing(monkeypatch, caplog):
    from mxnet_tpu import runtime
    monkeypatch.setattr(runtime, "_LIB", None)
    monkeypatch.setattr(runtime, "_TRIED", False)
    monkeypatch.setattr(runtime, "_ERROR", None)
    monkeypatch.setattr(runtime, "_lib_path", lambda: "/nonexistent/x.so")
    monkeypatch.setattr(runtime, "_src_dir", lambda: "/nonexistent/src")
    with caplog.at_level("WARNING", logger="mxnet_tpu"):
        assert runtime.get_lib() is None
    assert "no source directory" in runtime.load_error()
    assert "pure-python" in caplog.text
    with pytest.raises(RuntimeError, match="no source directory"):
        runtime.NativeStoragePool()


def test_kernel_case_arguments_are_made_from_a_seed():
    """make_args on every fill kind (the real table's arrays are too big
    to build in a test; its shapes compile in test_tpu_aot_compile.py)."""
    import jax.numpy as jnp
    import numpy as np
    fills = {c_fill if isinstance(c_fill, str) else c_fill[0]
             for c in chip_smoke.kernel_cases() for _, _, c_fill in c.args}
    case = chip_smoke.KernelCase("tiny", None, (
        ((4, 8), jnp.bfloat16, "normal"), ((8,), jnp.float32, "positive"),
        ((16,), jnp.int32, ("randint", 0, 5)),
        ((2, 3), jnp.int32, ("pages", 7))), None, 0.0)
    assert fills == {"normal", "positive", "randint", "pages"}
    a, b, c, d = chip_smoke.make_args(case, seed=3)
    again = chip_smoke.make_args(case, seed=3)
    assert all(np.array_equal(x, y) for x, y in zip((a, b, c, d), again))
    assert a.dtype == jnp.bfloat16 and (np.asarray(b) > 0).all()
    assert c.dtype == jnp.int32 and np.asarray(c).max() < 5
    # distinct live pages, never the scratch page 0
    assert sorted(np.asarray(d).ravel()) == sorted(set(np.asarray(d).ravel()))
    assert 1 <= np.asarray(d).min() and np.asarray(d).max() <= 6


_CASES = chip_smoke.kernel_cases()


@pytest.mark.parametrize("case", _CASES, ids=[c.name for c in _CASES])
def test_kernel_reference_answers_in_the_kernels_shape(case):
    """Traced, not run: each case's pure-JAX reference takes the kernel's
    arguments and returns what the kernel returns."""
    args = [jax.ShapeDtypeStruct(s, d) for s, d, _ in case.args]
    # shapes and dtypes, of every array where a case returns several
    assert jax.eval_shape(case.fn, *args) == jax.eval_shape(case.ref, *args)
