"""Pipeline (pp) and expert (ep) parallelism on the 8-virtual-device
mesh: the remaining two axes of the dp/tp/pp/sp/ep matrix.

Correctness bar: the parallel result must equal the plain sequential
computation of the same parameters, forward AND backward.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from mxnet_tpu.parallel import (make_pipeline, stack_stage_params,
                                make_mesh)

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs the 8-virtual-device mesh")


def _stage_fn(params, x):
    return jax.nn.relu(x @ params["w"] + params["b"])


def _stage_params(n_stage, d, seed=0):
    rng = np.random.RandomState(seed)
    return [{"w": jnp.asarray(rng.randn(d, d).astype("f4") / np.sqrt(d)),
             "b": jnp.asarray(rng.randn(d).astype("f4") * 0.1)}
            for _ in range(n_stage)]


def _sequential(stages, x):
    for p in stages:
        x = _stage_fn(p, x)
    return x


@pytest.mark.parametrize("pp,n_micro", [(2, 4), (4, 4), (4, 8)])
def test_pipeline_matches_sequential(pp, n_micro):
    d, batch = 16, 16
    mesh = make_mesh({"pp": pp}, devices=jax.devices()[:pp])
    stages = _stage_params(pp, d)
    stacked = stack_stage_params(stages, mesh, "pp")
    pipe = make_pipeline(_stage_fn, mesh, "pp", n_microbatch=n_micro)
    x = jnp.asarray(np.random.RandomState(1).randn(batch, d).astype("f4"))
    out = jax.jit(pipe)(stacked, x)
    ref = _sequential(stages, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


def test_pipeline_gradients_match_sequential():
    pp, d, batch = 4, 8, 8
    mesh = make_mesh({"pp": pp}, devices=jax.devices()[:pp])
    stages = _stage_params(pp, d, seed=3)
    stacked = stack_stage_params(stages, mesh, "pp")
    pipe = make_pipeline(_stage_fn, mesh, "pp", n_microbatch=4)
    x = jnp.asarray(np.random.RandomState(2).randn(batch, d).astype("f4"))

    def loss_pipe(p):
        return jnp.sum(pipe(p, x) ** 2)

    def loss_seq(plist):
        return jnp.sum(_sequential(plist, x) ** 2)

    g_pipe = jax.jit(jax.grad(loss_pipe))(stacked)
    g_seq = jax.grad(loss_seq)(stages)
    for i in range(pp):
        np.testing.assert_allclose(np.asarray(g_pipe["w"][i]),
                                   np.asarray(g_seq[i]["w"]),
                                   rtol=5e-4, atol=5e-5)
        np.testing.assert_allclose(np.asarray(g_pipe["b"][i]),
                                   np.asarray(g_seq[i]["b"]),
                                   rtol=5e-4, atol=5e-5)


# ---------------------------------------------------------------- experts
# (the top-1 capacity-drop ``moe_layer`` and its six tests went with PR 27:
# ``parallel/moe.py`` is now the one expert layer, told which experts it
# holds; tests/test_kimi_linear.py holds its other tests)

@pytest.mark.parametrize("ep", [2, 4])
def test_expert_shares_on_their_own_devices_add_up(ep):
    """Each of ``ep`` devices holds 8 / ep of the experts and routes over
    all 8: the parts they compute, each on its own device, add up to the
    layer one device computes whole. No token is dropped on the way."""
    from mxnet_tpu.parallel import expert_layer
    rng = np.random.RandomState(ep)
    n, d, h, e, k = 64, 8, 16, 8, 2
    x = jnp.asarray(rng.randn(n, d).astype("f4"))
    w_r = jnp.asarray(rng.randn(e, d).astype("f4"))
    bias = jnp.zeros(e)
    wg, wu = (jnp.asarray(rng.randn(e, h, d).astype("f4") * .3)
              for _ in range(2))
    wd = jnp.asarray(rng.randn(e, d, h).astype("f4") * .3)
    whole, counts = expert_layer(x, w_r, bias, wg, wu, wd,
                                 experts_held=(0, e), top_k=k, scale=2.0)
    assert int(counts.sum()) == n * k
    total, held = 0, 0
    for rank, dev in enumerate(jax.devices()[:ep]):
        lo, hi = rank * e // ep, (rank + 1) * e // ep
        put = lambda a: jax.device_put(a, dev)       # noqa: E731
        part, c = jax.jit(lambda *a, lo=lo, hi=hi: expert_layer(
            *a, experts_held=(lo, hi), top_k=k, scale=2.0))(
                put(x), put(w_r), put(bias), put(wg[lo:hi]), put(wu[lo:hi]),
                put(wd[lo:hi]))
        assert part.devices() == {dev}
        total = total + np.asarray(part)
        held += int(c.sum())
    assert held == n * k
    np.testing.assert_allclose(total, np.asarray(whole), rtol=2e-4,
                               atol=2e-5)
