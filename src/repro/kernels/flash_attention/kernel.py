"""Pallas TPU causal flash-attention forward kernel (training / dense path).

Same tiled-softmax core as the paged kernels, without the page indirection.
Grid (B·Hkv, num_q_blocks, num_kv_blocks); the GQA group is packed into the
Q-block rows exactly as in the paged Q-Block kernel (paper §4.4), giving the
MXU (block_q · G) rows per matmul. Causal skipping: KV blocks strictly above
the diagonal are masked out AND their index maps clamp to the last useful
block so the pipeline skips the dead DMAs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _dot(a, b, trans_b=False):
    dn = (((1,), (1 if trans_b else 0,)), ((), ()))
    return jax.lax.dot_general(a, b, dn, preferred_element_type=jnp.float32)


def _fwd_kernel(
    q_ref,  # [1, 1, BM, D]   BM = block_q * G (row = tok*G + g)
    k_ref,  # [1, 1, kvb, D]
    v_ref,
    o_ref,  # [1, 1, BM, D]
    acc_ref,
    m_ref,
    l_ref,
    *,
    block_q: int,
    kv_block: int,
    group: int,
    scale: float,
    causal: bool,
    q_offset: int,
):
    qi = pl.program_id(1)
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # absolute positions
    bm = q_ref.shape[2]
    row = jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
    q_pos = q_offset + qi * block_q + row // group  # [BM, 1]
    kv_start = ti * kv_block

    live = jnp.array(True)
    if causal:
        live = kv_start <= q_offset + (qi + 1) * block_q - 1

    @pl.when(live)
    def _():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0]
        v = v_ref[0]
        s = _dot(q, k, trans_b=True) * scale  # [BM, kvb]
        kv_pos = kv_start + jax.lax.broadcasted_iota(
            jnp.int32, (1, kv_block), 1
        )
        mask = kv_pos <= q_pos if causal else jnp.full(s.shape, True)
        s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        m_safe = jnp.where(m_new <= _NEG_INF, 0.0, m_new)
        p = jnp.exp(jnp.where(mask, s - m_safe, _NEG_INF))
        alpha = jnp.where(m_prev <= _NEG_INF, 0.0, jnp.exp(m_prev - m_safe))
        l_ref[...] = jnp.broadcast_to(
            l_ref[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True),
            l_ref.shape,
        )
        acc_ref[...] = acc_ref[...] * alpha + _dot(p.astype(v.dtype), v)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(ti == pl.num_programs(2) - 1)
    def _():
        l = l_ref[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def flash_attention_fwd(
    q: jax.Array,  # [BH, nq, BM, D]  (packed by ops.py; BH = B*Hkv)
    k: jax.Array,  # [BH, Skv, D]
    v: jax.Array,
    *,
    block_q: int,
    kv_block: int,
    group: int,
    scale: float,
    causal: bool,
    q_offset: int,
    interpret: bool = False,
) -> jax.Array:
    bh, nq, bm, d = q.shape
    skv = k.shape[1]
    nkv = skv // kv_block
    grid = (bh, nq, nkv)

    def kv_index_map(b, qi, ti):
        if causal:
            # clamp dead above-diagonal blocks to the last live one
            last_live = jax.lax.div(
                q_offset + (qi + 1) * block_q - 1, jnp.int32(kv_block)
            )
            ti = jnp.minimum(ti, last_live)
        return (b, ti, 0)

    kernel = functools.partial(
        _fwd_kernel,
        block_q=block_q,
        kv_block=kv_block,
        group=group,
        scale=scale,
        causal=causal,
        q_offset=q_offset,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bm, d), lambda b, qi, ti: (b, qi, 0, 0)),
            pl.BlockSpec((1, kv_block, d), kv_index_map),
            pl.BlockSpec((1, kv_block, d), kv_index_map),
        ],
        out_specs=pl.BlockSpec((1, 1, bm, d), lambda b, qi, ti: (b, qi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, d), jnp.float32),
            pltpu.VMEM((bm, 128), jnp.float32),
            pltpu.VMEM((bm, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v)
