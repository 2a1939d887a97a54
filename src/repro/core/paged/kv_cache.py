"""Paged KV cache pytrees + page read/write primitives.

Layout (stacked across attention layers, leading dim L):
  kv_pages        : [L, Hkv, num_pools, pages_per_pool, page_size, Dk + Dv]
                    one row per token and KV head: K in the first Dk lanes,
                    V in the last Dv (MLA: Dv = 0, V is a view of K), so a
                    64-wide head still fills 128 TPU lanes
  page_table      : [S, pages_per_seq] int32, POOL-LOCAL page ids
  pool of seq s   : s // (S // num_pools)

The pool is ONE array for the whole model.  A step carries it through the
layer scan and writes each layer's new rows in place; readers take the
whole stack plus a layer index, never a sliced-out layer.

`num_pools` is the data-parallel degree: each DP shard owns one page pool
and the sequences resident on it — pages are pooled (true PagedAttention
sharing) *within* a shard, and every gather/scatter below is batched over
the pool axis, so GSPMD keeps all page traffic shard-local (no cross-chip
page gathers). A single host (the serving engine on CPU, or any one chip)
is simply num_pools=1.

Page 0 of every pool is the NULL page: never allocated, target of padded
block-table entries — what keeps every lowered program fully static
(paper C5).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


class ShardingError(ValueError):
    """A launch or mesh configuration violates the sharding contract.

    Raised with the offending shapes in the message wherever a paged
    kernel requires a shard-local (single-pool) view, or where a mesh
    executor cannot split the model as requested (head counts not
    divisible by tp, missing devices, unsupported engine path).
    """


def require_single_pool(kv_pages: jax.Array, site: str):
    """Paged kernels run on a shard-local pool view: [L, Hkv, 1, P, ps, W].

    The pool axis is the data-parallel degree; anything >1 must be split
    by the caller (shard_map / per-pool vmap) before reaching a kernel.
    """
    pool_axis = kv_pages.ndim - 4
    if kv_pages.shape[pool_axis] != 1:
        raise ShardingError(
            f"{site}: expected a shard-local single-pool KV view but got "
            f"num_pools={kv_pages.shape[pool_axis]} (kv_pages shape "
            f"{tuple(kv_pages.shape)}); split the pool axis across the mesh "
            f"before launching the kernel"
        )


def local_kv_heads(num_kv_heads: int, num_devices: int,
                   *, num_q_heads: int | None = None) -> int:
    """Per-device KV head count under head-axis tensor parallelism.

    Whole heads per device keeps every page gather shard-local and the
    math bit-identical, so both head counts must divide evenly.
    """
    if num_kv_heads % num_devices:
        raise ShardingError(
            f"cannot shard num_kv_heads={num_kv_heads} across "
            f"tp={num_devices} devices: the KV pool is split on the head "
            f"axis in whole heads (num_kv_heads % tp must be 0)"
        )
    if num_q_heads is not None and num_q_heads % num_devices:
        raise ShardingError(
            f"cannot shard num_q_heads={num_q_heads} across "
            f"tp={num_devices} devices: query heads are split in whole "
            f"GQA groups (num_q_heads % tp must be 0)"
        )
    return num_kv_heads // num_devices


def shard_cache_specs(specs: dict, num_devices: int) -> dict:
    """Per-device view of `make_kv_cache_specs` output: the head axis
    (dim 1) is divided across the mesh, everything else is replicated."""
    out = {}
    for name, s in specs.items():
        local_kv_heads(s.shape[1], num_devices)
        shape = list(s.shape)
        shape[1] //= num_devices
        out[name] = jax.ShapeDtypeStruct(tuple(shape), s.dtype)
    return out


def make_kv_cache_specs(num_layers, num_kv_heads, num_pools, pages_per_pool,
                        page_size, k_dim, v_dim, dtype):
    """ShapeDtypeStruct specs — v_dim 0 means V is a view into the latent K
    rows (MLA)."""
    return {
        "kv_pages": jax.ShapeDtypeStruct(
            (num_layers, num_kv_heads, num_pools, pages_per_pool, page_size,
             k_dim + v_dim), dtype
        )
    }


def physical_slots(page_table: jax.Array, positions: jax.Array,
                   valid: jax.Array, page_size: int,
                   pages_per_pool: int) -> jax.Array:
    """positions [S, T] in-sequence positions -> pool-local flat slots
    [S, T]; invalid entries -> out-of-range trash slot (scatter-dropped)."""
    page = jnp.clip(positions, 0, None) // page_size
    off = jnp.clip(positions, 0, None) % page_size
    page = jnp.minimum(page, page_table.shape[1] - 1)
    phys = jnp.take_along_axis(page_table, page, axis=1) * page_size + off
    return jnp.where(valid, phys, pages_per_pool * page_size)


def write_pages(pool: jax.Array, layer, new: jax.Array, slots: jax.Array):
    """XLA scatter of new rows into layer `layer`: pool [L, Hkv, G, P, ps,
    W]; new [S, T, Hkv, W]; slots [S, T] pool-local flat slots. S = G *
    B_loc. Batched (per-pool) scatter; out-of-range slots dropped.  The
    xla backend's write (the pallas backend writes in place with
    `paged_attention.ops.write_rows`)."""
    nl, hkv, g, p, ps, w = pool.shape
    s, t = slots.shape
    b_loc = s // g
    flat = pool.reshape(nl, hkv, g, p * ps, w)
    upd = new.transpose(2, 0, 1, 3).reshape(hkv, g, b_loc * t, w)
    slots3 = slots.reshape(g, b_loc * t)
    garr = jnp.broadcast_to(jnp.arange(g)[:, None], (g, b_loc * t))
    # scalar layer + [G, B*T] index arrays around a slice: the indexed
    # dims lead the update, the head dim follows
    flat = flat.at[layer, :, garr, slots3, :].set(
        upd.transpose(1, 2, 0, 3), mode="drop")
    return flat.reshape(pool.shape)


def gather_pages(pool: jax.Array, layer, page_table: jax.Array) -> jax.Array:
    """Layer `layer` of [L, Hkv, G, P, ps, W] + [S, Np] -> [S, Np*ps, Hkv,
    W] dense per-seq rows. Batched over pools: stays shard-local under
    GSPMD."""
    pages = pool[layer]
    hkv, g, p, ps, w = pages.shape
    s, np_ = page_table.shape
    b_loc = s // g
    pt = page_table.reshape(1, g, b_loc * np_, 1, 1)
    out = jnp.take_along_axis(pages[:, :, None], pt[..., None], axis=3)
    # out: [Hkv, G, 1->B*Np broadcast, ...] -> [Hkv, G, B*Np, ps, W]
    out = out.reshape(hkv, g, b_loc, np_, ps, w)
    return out.transpose(1, 2, 3, 4, 0, 5).reshape(s, np_ * ps, hkv, w)
