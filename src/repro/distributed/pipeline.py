"""GPipe-style pipeline parallelism over a 'pipe' mesh axis (shard_map +
collective_permute).

Not enabled for the graded 16x16 / 2x16x16 meshes — every assigned arch fits
with TP+FSDP there (DESIGN.md §4) — but provided, tested on host devices,
and ready for >2-pod scale-out where the 'pod' axis converts to 'pipe'.

Schedule: classic GPipe fill-drain over M microbatches and S stages
(bubble fraction (S-1)/(M+S-1)); each tick every stage computes one resident
microbatch then ppermutes its activation to the next stage.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipeline_apply(
    stage_fn,
    stage_params,  # pytree with leading [num_stages] dim, sharded on 'pipe'
    x,  # [M, mb, ...] microbatched input (stage-0 input)
    *,
    mesh: Mesh,
    axis: str = "pipe",
):
    """Runs y = stage_{S-1}(... stage_0(x)) with each stage resident on one
    'pipe' shard. Returns [M, mb, ...] outputs (from the last stage)."""
    num_stages = mesh.shape[axis]
    m = x.shape[0]
    ticks = m + num_stages - 1

    def shard_body(params_local, x_local):
        # params_local: this stage's params (leading dim 1); x_local: [M,...]
        params_l = jax.tree.map(lambda t: t[0], params_local)
        sid = jax.lax.axis_index(axis)
        perm = [(i, i + 1) for i in range(num_stages - 1)]

        def tick(carry, t):
            buf, outs = carry  # buf: activation resident on this stage
            mb_idx = t - sid  # microbatch this stage works on at tick t
            feed = jax.lax.dynamic_index_in_dim(
                x_local, jnp.clip(mb_idx, 0, m - 1), 0, keepdims=False
            )
            cur = jnp.where(sid == 0, feed, buf)
            active = (mb_idx >= 0) & (mb_idx < m)
            y = stage_fn(params_l, cur)
            y = jnp.where(active, y, buf)
            # emit finished microbatch on the last stage
            upd = jax.lax.dynamic_update_index_in_dim(
                outs, y, jnp.clip(mb_idx, 0, m - 1), 0
            )
            outs = jnp.where(active & (sid == num_stages - 1), upd, outs)
            nxt = jax.lax.ppermute(y, axis, perm)
            return (nxt, outs), None

        # the carry becomes device-varying after ppermute; mark it as such
        buf0 = jax.lax.pcast(jnp.zeros_like(x_local[0]), (axis,),
                             to="varying")
        outs0 = jax.lax.pcast(jnp.zeros_like(x_local), (axis,), to="varying")
        (_, outs), _ = jax.lax.scan(
            tick, (buf0, outs0), jnp.arange(ticks)
        )
        # every stage holds zeros except the last; psum broadcasts results
        return jax.lax.psum(outs, axis)

    pspec = jax.tree.map(lambda _: P(axis), stage_params)
    return jax.shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(axis), stage_params), P()),
        out_specs=P(),
    )(stage_params, x)


def stage_split(params_stacked, num_stages: int):
    """[L, ...] layer-stacked params -> [S, L/S, ...] stage-stacked."""
    return jax.tree.map(
        lambda t: t.reshape((num_stages, t.shape[0] // num_stages)
                            + t.shape[1:]),
        params_stacked,
    )
