"""Pallas TPU kernel for the chunked Mamba2/SSD layer.

One grid cell per (batch, head, chunk); the chunk axis is the innermost
sequential dimension and the SSM state [N, P] lives in VMEM scratch, carried
across chunks (the inter-chunk scan), while the intra-chunk work is two
MXU matmuls ([Q,N]·[N,Q] decayed score matrix and [Q,Q]·[Q,P] output) — the
TPU-native shape of the SSD algorithm. dt is pre-absorbed into x (xdt) by
ops.py so every in-kernel operand is a clean 2-D tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dot(a, b, trans_a=False, trans_b=False):
    dn = (((0 if trans_a else 1,), (1 if trans_b else 0,)), ((), ()))
    return jax.lax.dot_general(a, b, dn, preferred_element_type=jnp.float32)


def _ssd_kernel(
    # inputs
    xdt_ref,  # [1, 1, 1, Q, P]   dt_j * x_j
    b_ref,  # [1, 1, 1, Q, N]
    c_ref,  # [1, 1, 1, Q, N]
    acum_ref,  # [1, 1, 1, Q]      inclusive cumsum of dt*A within chunk
    s0_ref,  # [1, 1, N, P]      initial state
    # outputs
    y_ref,  # [1, 1, 1, Q, P]
    sfin_ref,  # [1, 1, N, P]
    # scratch
    s_ref,  # [N, P] f32
    *,
    chunk: int,
):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _():
        s_ref[...] = s0_ref[0, 0].astype(jnp.float32)

    xdt = xdt_ref[0, 0, 0].astype(jnp.float32)  # [Q, P]
    bmat = b_ref[0, 0, 0].astype(jnp.float32)  # [Q, N]
    cmat = c_ref[0, 0, 0].astype(jnp.float32)  # [Q, N]
    a_cum = acum_ref[0, 0, 0].astype(jnp.float32)  # [Q]
    a_tot = a_cum[chunk - 1]

    # intra-chunk: causal decayed scores
    scores = _dot(cmat, bmat, trans_b=True)  # [Q, Q]
    seg = a_cum[:, None] - a_cum[None, :]
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lmat = jnp.where(row >= col, jnp.exp(seg), 0.0)
    y = _dot(scores * lmat, xdt)  # [Q, P]

    # inter-chunk: contribution of the state entering this chunk
    s_in = s_ref[...]
    y += jnp.exp(a_cum)[:, None] * _dot(cmat, s_in)

    # state update: S_out = exp(a_tot)·S_in + Σ_j exp(a_tot - a_cum_j) B_j xdt_j^T
    w = jnp.exp(a_tot - a_cum)  # [Q]
    s_ref[...] = jnp.exp(a_tot) * s_in + _dot(
        bmat * w[:, None], xdt, trans_a=True
    )

    y_ref[0, 0, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == pl.num_programs(2) - 1)
    def _():
        sfin_ref[0, 0] = s_ref[...].astype(sfin_ref.dtype)


def ssd_chunked_fwd(
    xdt: jax.Array,  # [B, H, nc, Q, P]
    b: jax.Array,  # [B, H, nc, Q, N]
    c: jax.Array,  # [B, H, nc, Q, N]
    a_cum: jax.Array,  # [B, H, nc, Q]
    s0: jax.Array,  # [B, H, N, P]
    *,
    interpret: bool = False,
):
    bsz, h, nc, q, p = xdt.shape
    n = b.shape[-1]
    grid = (bsz, h, nc)
    kernel = functools.partial(_ssd_kernel, chunk=q)
    y, s_fin = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, q, p), lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec((1, 1, 1, q, n), lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec((1, 1, 1, q, n), lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec((1, 1, 1, q), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, n, p), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, 1, q, p), lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec((1, 1, n, p), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct(xdt.shape, xdt.dtype),
            jax.ShapeDtypeStruct(s0.shape, jnp.float32),
        ),
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="mamba2_ssd_chunked",
    )(xdt, b, c, a_cum, s0)
    return y, s_fin
