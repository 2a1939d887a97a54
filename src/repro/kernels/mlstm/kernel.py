"""Pallas TPU kernel for the stabilized chunkwise mLSTM (xLSTM).

Same structure as the Mamba2 SSD kernel: grid (B, H, chunks), chunk axis
sequential, matrix memory C [P,P] + normalizer n [P] + stabilizer m carried
in VMEM scratch; intra-chunk work is MXU matmuls over decayed score
matrices. Gate cumulants (bcum, cummax g) are precomputed by ops.py.
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


def _mlstm_kernel(
    q_ref,  # [1, 1, 1, Q, P]
    k_ref,
    v_ref,
    ig_ref,  # [1, 1, 1, Q]
    bcum_ref,  # [1, 1, 1, Q]
    g_ref,  # [1, 1, 1, Q]   cummax(ig - bcum)
    h_ref,  # out [1, 1, 1, Q, P]
    cfin_ref,  # out [1, 1, P, P]
    nfin_ref,  # out [1, 1, 1, P]
    mfin_ref,  # out [1, 1, 1]
    c_sc,  # scratch [P, P] f32
    n_sc,  # scratch [1, P] f32
    m_sc,  # scratch [1, 128] f32
    *,
    chunk: int,
):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _():
        c_sc[...] = jnp.zeros_like(c_sc)
        n_sc[...] = jnp.zeros_like(n_sc)
        m_sc[...] = jnp.full_like(m_sc, -jnp.inf)

    qc = q_ref[0, 0, 0].astype(jnp.float32)  # [Q, P]
    kc = k_ref[0, 0, 0].astype(jnp.float32)
    vc = v_ref[0, 0, 0].astype(jnp.float32)
    igc = ig_ref[0, 0, 0].astype(jnp.float32)  # [Q]
    bc = bcum_ref[0, 0, 0].astype(jnp.float32)
    gc = g_ref[0, 0, 0].astype(jnp.float32)
    ftot = bc[chunk - 1]
    gq = gc[chunk - 1]
    m_in = m_sc[0, 0]
    c_in = c_sc[...]
    n_in = n_sc[...]  # [1, P]

    m_i = bc + jnp.maximum(m_in, gc)  # [Q]
    w = bc[:, None] - bc[None, :] + igc[None, :] - m_i[:, None]  # [Qi, Qj]
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    dmat = jnp.where(row >= col, jnp.exp(w), 0.0)
    scores = _dot(qc, kc, trans_b=True) * dmat  # [Q, Q]
    num = _dot(scores, vc)  # [Q, P]
    den_vec = _dot(dmat, kc)  # [Q, P]
    w_in = jnp.exp(bc + m_in - m_i)  # [Q]
    num += w_in[:, None] * _dot(qc, c_in)
    den_vec += w_in[:, None] * n_in
    den = jnp.maximum(
        jnp.abs(jnp.sum(qc * den_vec, axis=1)), jnp.exp(-m_i)
    )  # [Q]
    h_ref[0, 0, 0] = (num / den[:, None]).astype(h_ref.dtype)

    m_out = ftot + jnp.maximum(m_in, gq)
    w_state = jnp.exp(ftot - bc + igc - m_out)  # [Q]
    decay = jnp.exp(ftot + m_in - m_out)
    c_sc[...] = decay * c_in + _dot(kc * w_state[:, None], vc, trans_a=True)
    n_sc[...] = decay * n_in + jnp.sum(kc * w_state[:, None], axis=0)[None, :]
    m_sc[...] = jnp.full_like(m_sc, m_out)

    @pl.when(ci == pl.num_programs(2) - 1)
    def _():
        cfin_ref[0, 0] = c_sc[...]
        nfin_ref[0, 0] = n_sc[...]
        mfin_ref[0, 0, 0] = m_sc[0, 0]


def mlstm_chunked_fwd(
    q: jax.Array,  # [B, H, nc, Q, P]
    k: jax.Array,
    v: jax.Array,
    ig: jax.Array,  # [B, H, nc, Q]
    bcum: jax.Array,
    g: jax.Array,
    *,
    interpret: bool = False,
):
    bsz, h, nc, qlen, p = q.shape
    grid = (bsz, h, nc)
    kernel = functools.partial(_mlstm_kernel, chunk=qlen)
    qkv_spec = pl.BlockSpec(
        (1, 1, 1, qlen, p), lambda bi, hi, ci: (bi, hi, ci, 0, 0)
    )
    gate_spec = pl.BlockSpec(
        (1, 1, 1, qlen), lambda bi, hi, ci: (bi, hi, ci, 0)
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[qkv_spec, qkv_spec, qkv_spec, gate_spec, gate_spec, gate_spec],
        out_specs=(
            qkv_spec,
            pl.BlockSpec((1, 1, p, p), lambda bi, hi, ci: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, 1, p), lambda bi, hi, ci: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda bi, hi, ci: (bi, hi, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((bsz, h, p, p), jnp.float32),
            jax.ShapeDtypeStruct((bsz, h, 1, p), jnp.float32),
            jax.ShapeDtypeStruct((bsz, h, 1), jnp.float32),
        ),
        scratch_shapes=[
            pltpu.VMEM((p, p), jnp.float32),
            pltpu.VMEM((1, p), jnp.float32),
            pltpu.VMEM((1, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="mlstm_chunked",
    )(q, k, v, ig, bcum, g)
    return out
