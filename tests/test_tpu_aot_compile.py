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
    assert set(kernels.KERNEL_OPS) | {"pallas_flash", "pallas_kda",
                                      "pallas_ssd"} == covered


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


def test_the_ssd_kernels_run_under_the_scan_scope(one_chip):
    """Where ``ssm_ms.train`` finds Mamba-2's core: in the compiled text
    of a core's forward and gradient (heads of 64, a state of 128, chunks
    of 128, bfloat16) the kernels' custom calls carry ``mx/ssm/scan`` in
    their ``op_name``: the forward's, and the backward's two (the states on
    entry to the chunks, the walk back) through the name the backward
    enters; the forward of the group the core's backward recomputes is
    gone, its outputs unused. ``delta`` and the running sums stay in XLA
    under ``mx/ssm/intra``."""
    import re
    import jax.numpy as jnp
    from mxnet_tpu.kernels import tier
    from mxnet_tpu.ops import lm_ops
    h, p, n, t = 4, 64, 128, 512

    def S(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(*a):
        # two groups of two chunks: the first group's last state is what
        # keeps the forward's kernel in the gradient's program
        return jnp.sum(lm_ops.ssd_chunked(*a, num_heads=h, chunk=128,
                                          group=2).astype(jnp.float32))
    with tier.force_compiled():
        text = jax.jit(jax.grad(loss, argnums=range(7))).lower(
            S((1, t, h * p), jnp.bfloat16), S((1, t, n), jnp.bfloat16),
            S((1, t, n), jnp.bfloat16), S((1, t, h), jnp.bfloat16),
            S((h,)), S((h,)), S((h,))).compile().as_text()
    calls = re.findall(r'^\s*%(ssd_scan_\w+)[.\d]* = .*metadata=\{op_name='
                       r'"([^"]*)"', text, re.M)
    assert sorted(k for k, _ in calls) == ["ssd_scan_bwd", "ssd_scan_fwd",
                                           "ssd_scan_states"]
    for k, op in calls:
        assert "mx/ssm/scan" in op, (k, op)
        assert ("transpose(jvp(mx/ssm/scan))" in op) == (k != "ssd_scan_fwd")
    assert re.search(r'op_name="[^"]*mx/ssm/intra[^"]*"', text)


# the attention core of each language-model cell, 8,192 tokens in bfloat16:
# (heads, KV heads, q.k width, v width, window, scale, the scope its model
# names, whether the backward kernel takes it)
_CORES = {
    "ouro": (16, 16, 128, 128, None, None, "mx/attn/full", True),
    "kimi_mla": (32, 32, 192, 128, None, None, "mx/mla", True),
    "trinity_window": (32, 4, 128, 128, 2048, None, "mx/attn/window", True),
    "trinity_full": (32, 4, 128, 128, None, None, "mx/attn/full", False),
    "granite": (32, 8, 64, 64, None, 0.015625, "mx/attn/full", False),
}


@pytest.mark.parametrize("cell", sorted(_CORES))
def test_the_backward_kernel_lowers_where_the_shapes_take_it(one_chip, cell):
    """The gradient of ``_contrib_FlashAttention`` at a cell's core,
    compiled for a described v5e under the scope its model names: the
    backward is the custom call ``flash_bwd``, under that scope, where
    ``pallas_flash.bwd_eligible`` takes the shapes, and absent (the plain
    blockwise backward) where it declines them."""
    import re
    import jax.numpy as jnp
    from mxnet_tpu.kernels import tier
    from mxnet_tpu.ops import pallas_flash, registry
    h, hk, d, dv, window, scale, scope, kernel = _CORES[cell]
    t = 8192
    assert pallas_flash.bwd_eligible(t, t, d, dv, h // hk, window,
                                     jnp.bfloat16) == kernel
    op = registry.get("_contrib_FlashAttention").fn

    def S(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        with jax.named_scope(scope):
            o = op(q, k, v, causal=True, window=window, scale=scale)
        return jnp.sum(o.astype(jnp.float32))
    with tier.force_compiled():
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            S((1, h, t, d)), S((1, hk, t, d)), S((1, hk, t, dv))
        ).compile().as_text()
    calls = re.findall(r'^\s*%(flash_bwd)[.\d]* = .*metadata=\{op_name='
                       r'"([^"]*)"', text, re.M)
    assert len(calls) == int(kernel)
    for _, name in calls:
        assert "transpose(jvp(%s))" % scope in name
