"""Pipeline parallelism (GPipe schedule) over a mesh axis via shard_map.

At two pods, the natural deployment pipelines *across pods* — the "pod"
axis rides the slower DCN links, and pipelining converts its traffic from
per-layer tensor exchanges into one boundary activation per microbatch
per tick.  The same machinery pipelines over any axis.

Mechanics (classic SPMD pipeline): every device holds the layer stack of
its stage.  Microbatches enter at stage 0; each tick every stage applies
its layers to its current slot and the slot rotates one stage forward via
``lax.ppermute``.  ``n_micro + n_stages - 1`` ticks drain the pipeline.
Bubble fraction = (S-1)/(M+S-1) — choose n_micro >> n_stages.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def spmd_pipeline(stage_fn, axis_name: str, n_micro: int):
    """Build the per-device pipeline body (call under shard_map).

    stage_fn(stage_params, x) -> y — applies ONE stage's layers.
    Returns body(stage_params, x_micro) with x_micro [n_micro, mb, ...]
    resident on every device (only stage 0 consumes it); the output is the
    stacked microbatch outputs, valid on the LAST stage.
    """

    def body(stage_params, x_micro):
        n_stages = jax.lax.psum(1, axis_name)
        stage_id = jax.lax.axis_index(axis_name)
        mb_shape = x_micro.shape[1:]
        ticks = n_micro + n_stages - 1

        def tick(carry, t):
            slot, outputs = carry
            # stage 0 ingests microbatch t (when available)
            take = jnp.clip(t, 0, n_micro - 1)
            fresh = jax.lax.dynamic_index_in_dim(x_micro, take, 0, keepdims=False)
            slot = jnp.where(stage_id == 0, fresh, slot)
            y = stage_fn(stage_params, slot)
            # last stage emits microbatch t - (n_stages - 1)
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            emit = (t >= n_stages - 1) & (stage_id == n_stages - 1)
            outputs = jax.lax.cond(
                emit,
                lambda o: jax.lax.dynamic_update_index_in_dim(o, y, out_idx, 0),
                lambda o: o,
                outputs,
            )
            # rotate stage outputs forward one stage
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            slot = jax.lax.ppermute(y, axis_name, perm)
            return (slot, outputs), None

        slot0 = jnp.zeros(mb_shape, x_micro.dtype)
        out0 = jnp.zeros((n_micro,) + mb_shape, x_micro.dtype)
        (slot, outputs), _ = jax.lax.scan(tick, (slot0, out0), jnp.arange(ticks))
        # broadcast the last stage's outputs to every device
        last = jax.lax.psum(
            jnp.where(stage_id == n_stages - 1, 1.0, 0.0)[None] * outputs.reshape(n_micro, -1),
            axis_name,
        )
        return last.reshape((n_micro,) + mb_shape)

    return body


def pipelined_apply(
    mesh: Mesh,
    stage_fn,
    params_stacked,  # leaves [n_stages, ...] — stage s holds slice s
    x: jnp.ndarray,  # [batch, ...] — split into n_micro microbatches
    *,
    pipe_axis: str = "pod",
    n_micro: int = 4,
):
    """Run ``stage_fn`` as a pipeline over ``pipe_axis`` of ``mesh``."""
    n_stages = mesh.shape[pipe_axis]
    b = x.shape[0]
    assert b % n_micro == 0, (b, n_micro)
    x_micro = x.reshape(n_micro, b // n_micro, *x.shape[1:])

    params_spec = jax.tree.map(lambda _: P(pipe_axis), params_stacked)
    other_axes = [a for a in mesh.axis_names if a != pipe_axis]

    body = spmd_pipeline(stage_fn, pipe_axis, n_micro)

    def per_stage(stage_params, xm):
        stage_params = jax.tree.map(lambda a: a[0], stage_params)  # strip stage dim
        return body(stage_params, xm)

    fn = jax.shard_map(
        per_stage,
        mesh=mesh,
        in_specs=(params_spec, P()),
        out_specs=P(),
        check_vma=False,
    )
    out = fn(params_stacked, x_micro)
    return out.reshape(b, *out.shape[2:])
