"""Test configuration.

Forces 8 virtual CPU devices so multi-chip sharding tests (mesh/pjit/
shard_map) run without TPU hardware — the strategy SURVEY.md §4 prescribes
as the analog of the reference's N-local-process dist tests
(ci/docker/runtime_functions.sh:901-930).

The suite runs on the CPU platform (`JAX_PLATFORMS=cpu` in the driver's
command, and pinned again below for a bare `pytest`); what the chip does
is shown by chip_smoke.py. Set MXNET_TEST_PLATFORM=tpu to run the same
suite against the chip (the reference's test_operator_gpu.py pattern).
"""
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
os.environ.setdefault("MXNET_TEST_DEVICE", "cpu")

import jax  # noqa: E402

# Pinned BEFORE mxnet_tpu is imported: the package decides at import
# whether the persistent compile cache is on, and it is off only for a
# CPU-pinned process.
if os.environ.get("MXNET_TEST_PLATFORM", "cpu") == "cpu":
    jax.config.update("jax_platforms", "cpu")
    # Custom-op tests escape to host via jax.pure_callback; with async CPU
    # dispatch the main thread races ahead and the callback's nested jax
    # work can starve the client's thread pool (a hard deadlock on
    # single-core CI boxes). Inline dispatch is deterministic and must be
    # set before the CPU client is created.
    jax.config.update("jax_cpu_enable_async_dispatch", False)

import pytest  # noqa: E402


def pytest_configure(config):
    # tier-1 CI runs `-m 'not slow'`; multi-process kill/restart drills
    # (minutes of wall clock) opt out of it with this marker
    config.addinivalue_line(
        "markers", "slow: multi-process / long-running test, excluded "
        "from the tier-1 fast suite")


@pytest.fixture
def ctx():
    from mxnet_tpu import test_utils
    return test_utils.default_context()


RESNET_STEP_BATCH = 128


@pytest.fixture(scope="session")
def resnet_step_text():
    """Pre-optimization StableHLO of the benched ResNet-50 fused step.

    One session-scoped lowering (a few seconds) shared by every chip-free
    HLO budget: the convert/transpose ratchets (test_step_hlo_budget) and
    the MXL505 fusion-bytes ratchet (test_lint_clean). Lowered at the
    bench batch with the default kernel tier — the committed budgets
    describe the program users get without opting in to anything."""
    if jax.devices()[0].platform != "cpu":
        pytest.skip("lowering analysis is defined for the CPU backend")
    import sys
    tools = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools")
    sys.path.insert(0, tools)
    try:
        from diagnose_step_hlo import build_fused, lower_step
    finally:
        sys.path.pop(0)
    mod = build_fused(RESNET_STEP_BATCH)
    return lower_step(mod).as_text()
