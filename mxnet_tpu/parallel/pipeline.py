"""Pipeline parallelism over a ``pp`` mesh axis (GPipe schedule).

Reference role: the reference has no pipeline engine — model parallelism
there is manual ``group2ctx`` placement (refused loudly by this
framework). The TPU-native design is the scaling-book recipe: stage
parameters carry a leading stage axis sharded over ``pp``; inside
``shard_map`` every device runs the SAME program — a ``lax.scan`` over
``n_micro + n_stage - 1`` ticks in which each device applies its stage to
whatever activation it holds and ``ppermute``s the result to the next
device. Bubble fraction is the GPipe (S-1)/(T) overhead; increase
microbatches to amortize. Differentiable end to end (ppermute has a
transpose rule), so ``jax.grad`` of a pipelined loss is the data-parallel
gradient.

The stage function is arbitrary jax (one or more layers); see
tests/test_pipeline_moe.py and __graft_entry__.dryrun_multichip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = ["make_pipeline", "stack_stage_params"]


def stack_stage_params(param_list, mesh=None, axis_name="pp"):
    """Stack per-stage pytrees into one pytree with a leading stage axis
    (sharded over ``axis_name`` when a mesh is given)."""
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *param_list)
    if mesh is not None:
        def put(x):
            spec = P(axis_name, *([None] * (x.ndim - 1)))
            return jax.device_put(x, NamedSharding(mesh, spec))
        stacked = jax.tree.map(put, stacked)
    return stacked


def make_pipeline(stage_fn, mesh, axis_name="pp", n_microbatch=None):
    """Build ``pipeline(stage_params, x) -> y`` running ``stage_fn`` as a
    GPipe pipeline over the mesh's ``axis_name`` dimension.

    * ``stage_fn(params_i, x) -> x`` — one stage's computation; every
      stage must map (micro_batch, d) -> (micro_batch, d_out) with a
      shape all stages share (the classic equal-width pipeline).
    * ``stage_params`` — pytree with leading axis ``n_stage`` (see
      stack_stage_params), sharded over ``axis_name``.
    * ``x`` — (batch, d); batch must divide into ``n_microbatch``.
    """
    from jax import shard_map

    n_stage = mesh.shape[axis_name]
    if n_microbatch is None:
        n_microbatch = n_stage

    def pipelined(stage_params, x):
        n_micro = n_microbatch
        if x.shape[0] % n_micro:
            raise ValueError(
                "pipeline batch %d must divide n_microbatch %d"
                % (x.shape[0], n_micro))
        micro = x.reshape(n_micro, x.shape[0] // n_micro, *x.shape[1:])

        # replication checker off: the psum-of-banked-zeros trick
        # confuses its static analysis (the result IS replicated)
        smap = functools.partial(shard_map, mesh=mesh,
                                 in_specs=(P(axis_name), P()),
                                 out_specs=P(), check_vma=False)

        @smap
        def run(params, micro_all):
            # params arrives with the leading stage axis sharded: this
            # device holds exactly its stage's slice, shape (1, ...)
            my_params = jax.tree.map(lambda p: p[0], params)
            stage = lax.axis_index(axis_name)
            right_perm = [(i, (i + 1) % n_stage) for i in range(n_stage)]
            T = n_micro + n_stage - 1
            mshape = micro_all.shape[1:]

            def tick(carry, t):
                buf, outs = carry
                # stage 0 ingests microbatch t (zeros once drained)
                feed = lax.dynamic_index_in_dim(
                    micro_all, jnp.minimum(t, n_micro - 1), 0,
                    keepdims=False)
                feed = jnp.where(t < n_micro, feed, jnp.zeros(mshape,
                                                              micro_all.dtype))
                inp = jnp.where(stage == 0, feed, buf)
                y = stage_fn(my_params, inp)
                # the LAST stage's output for microbatch m emerges at
                # tick t = m + n_stage - 1; bank it
                m = t - (n_stage - 1)
                outs = lax.cond(
                    m >= 0,
                    lambda o: lax.dynamic_update_index_in_dim(
                        o, jnp.where(stage == n_stage - 1, y,
                                     jnp.zeros_like(y)),
                        jnp.maximum(m, 0), 0),
                    lambda o: o, outs)
                # rotate activations one stage to the right
                buf = lax.ppermute(y, axis_name, right_perm)
                return (buf, outs), None

            buf0 = jnp.zeros(mshape, micro_all.dtype)
            outs0 = jnp.zeros((n_micro,) + mshape, micro_all.dtype)
            (_, outs), _ = lax.scan(tick, (buf0, outs0),
                                    jnp.arange(T))
            # every device banked zeros except the last stage: one psum
            # replicates the result
            return lax.psum(outs, axis_name)

        out = run(stage_params, micro)
        return out.reshape(x.shape[0], *out.shape[2:])

    return pipelined
