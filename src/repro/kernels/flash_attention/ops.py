"""Jitted flash-attention wrapper with a Pallas forward and a differentiable
XLA backward (recompute-based, matching the remat discipline of the train
loop; a dedicated Pallas backward kernel is listed as future work in
DESIGN.md)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import kernels
from repro.kernels.flash_attention import kernel as K
from repro.kernels.flash_attention.ref import flash_attention_xla
from repro.utils.misc import round_up

LANE = 128


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8)
)
def _flash(q, k, v, causal, scale, block_q, kv_block, q_offset, interpret):
    return _flash_fwd_impl(
        q, k, v, causal, scale, block_q, kv_block, q_offset, interpret
    )


def _flash_fwd_impl(q, k, v, causal, scale, block_q, kv_block, q_offset, interpret):
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    g = hq // hkv
    dp = round_up(d, LANE)
    if dp != d:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, dp - d)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, 0), (0, dp - d)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, dp - d)))
    nq = sq // block_q
    bm = block_q * g
    # pack: [B, Sq, Hq, D] -> [B*Hkv, nq, BM, D], row = tok*G + g
    qp = q.reshape(b, nq, block_q, hkv, g, dp).transpose(0, 3, 1, 2, 4, 5)
    qp = qp.reshape(b * hkv, nq, bm, dp)
    kp = k.transpose(0, 2, 1, 3).reshape(b * hkv, skv, dp)
    vp = v.transpose(0, 2, 1, 3).reshape(b * hkv, skv, dp)
    o = K.flash_attention_fwd(
        qp, kp, vp,
        block_q=block_q, kv_block=kv_block, group=g, scale=scale,
        causal=causal, q_offset=q_offset, interpret=interpret,
    )
    o = o.reshape(b, hkv, nq, block_q, g, dp).transpose(0, 2, 3, 1, 4, 5)
    return o.reshape(b, sq, hq, dp)[..., :d]


def _flash_vjp_fwd(q, k, v, causal, scale, block_q, kv_block, q_offset, interpret):
    o = _flash_fwd_impl(
        q, k, v, causal, scale, block_q, kv_block, q_offset, interpret
    )
    return o, (q, k, v)


def _flash_vjp_bwd(causal, scale, block_q, kv_block, q_offset, interpret, res, do):
    q, k, v = res

    def f(q, k, v):
        return flash_attention_xla(
            q, k, v, causal=causal, scale=scale, kv_block=kv_block,
            q_offset=q_offset,
        )

    _, vjp = jax.vjp(f, q, k, v)
    return vjp(do)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(
    q: jax.Array,  # [B, Sq, Hq, D]
    k: jax.Array,  # [B, Skv, Hkv, D]
    v: jax.Array,
    *,
    causal: bool = True,
    scale: float | None = None,
    block_q: int = 128,
    kv_block: int = 128,
    q_offset: int = 0,
    interpret: bool | None = None,
) -> jax.Array:
    """Causal GQA flash attention (Pallas fwd, exact XLA-recompute bwd)."""
    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / (d**0.5)
    sq, skv = q.shape[1], k.shape[1]
    block_q = min(block_q, sq)
    kv_block = min(kv_block, skv)
    assert sq % block_q == 0 and skv % kv_block == 0
    return _flash(
        q, k, v, causal, scale, block_q, kv_block, q_offset,
        kernels.interpret_mode(interpret),
    )
