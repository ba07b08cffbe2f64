"""Chip-free compiles of every Pallas kernel in the tree, for a TPU v5e.

The interpreter accepts kernels the chip's compiler refuses (a block the
tiling rejects, a primitive Mosaic has no lowering for), so each kernel
is compiled here with ``interpret=False`` at a width its users run, for a
``v5e:2x2`` that is described and not attached. The table of kernels and
widths is ``chip_smoke.kernel_cases()``: what compiles here is what
``chip_smoke.py`` runs on the chip and holds to a reference there.
Nothing executes here, and a compile that passes is not a chip run.

Only one process may hold the TPU library, so the topology is described
inside a module-scoped fixture (never at import, never in conftest) and
every such compile lives in this one file: see section 2 of
/opt/skills/guides/on-chip-measurement/SKILL.md.
"""
import os
import sys

import pytest

import jax
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import chip_smoke  # noqa: E402  (imports nothing but the stdlib)

CASES = chip_smoke.kernel_cases()   # shapes and closures; touches no device


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)


@pytest.fixture(scope="module")
def one_chip(topo):
    """SingleDeviceSharding on a described chip, with the persistent
    compile cache off around the module (an entry written for a described
    device cannot be read back without one, and warns)."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_every_kernel_module_has_a_case():
    """A kernel added to the tier without a case here would reach the
    chip uncompiled."""
    from mxnet_tpu import kernels
    covered = {c.name.split("[")[0] for c in CASES}
    assert set(kernels.KERNEL_OPS) | {"pallas_flash", "pallas_kda"} == covered


@pytest.mark.parametrize("case", CASES, ids=[c.name for c in CASES])
def test_kernel_compiles_for_v5e(one_chip, case):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype, _ in case.args]
    text = jax.jit(case.fn).lower(*args).compile().as_text()
    # a program without the custom call would mean the kernel was bypassed
    assert "tpu_custom_call" in text


def test_the_compiler_names_the_grouped_products_itself(one_chip):
    """Where the expert layer's scope stops (tests/test_device_scopes.py):
    the program traces ``lax.ragged_dot`` under ``mx/moe/experts``, and the
    TPU compiler makes each a kernel call of its own, ``%ragged-dot-none``,
    with ``op_name="ragged-dot-none"`` in place of the name stack. What is
    traced beside them keeps the scope. If this fails the compiler has
    begun to keep the name: then ``moe_ms.train`` counts the products."""
    import re
    import jax.numpy as jnp
    from mxnet_tpu.parallel import moe
    n, d, experts, held, h, k = 1024, 256, 16, 4, 256, 2

    def S(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(x, rw, rb, wg, wu, wd):
        y, _ = moe.expert_layer(x, rw, rb, wg, wu, wd,
                                experts_held=(0, held), top_k=k)
        return jnp.sum(y.astype(jnp.float32))
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 3, 4, 5))).lower(
        S((n, d)), S((experts, d), jnp.float32), S((experts,), jnp.float32),
        S((held, h, d)), S((held, h, d)), S((held, d, h))).compile().as_text()
    products = re.findall(
        r'^\s*%(ragged-dot-none[.\d]*) = .*metadata=\{op_name="([^"]*)"',
        text, re.M)
    assert len(products) >= 9       # three a pass: forward, again, backward
    assert {op for _, op in products} == {"ragged-dot-none"}
    assert re.search(r'op_name="[^"]*mx/moe/experts[^"]*"', text)
