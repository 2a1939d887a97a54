"""Pure-jnp oracles for the paged attention kernels.

Layout conventions (shared with kernel.py / ops.py):
  kv_pages         : [num_layers, num_kv_heads, num_pages, page_size,
                     2 * head_dim]: one row per token and KV head, K in its
                     first head_dim lanes and V in the rest; every function
                     takes the whole stack plus a `layer` index
  page_table       : [max_seqs, pages_per_seq] int32 (0-padded; entry j holds
                     the physical page of logical page j of that sequence)
  context_lens     : [max_seqs] int32 — number of *valid* tokens in the cache
                     for each sequence (0 for dead / padded slots). For decode
                     this INCLUDES the token written this step.

Decode:  q [max_seqs, num_q_heads, head_dim] -> out same shape. Each live
sequence attends its single query over cache positions [0, context_lens[s]).
Dead sequences produce exact zeros (the static-launch-grid contract, paper
§4.7/§6.2: excess instances are no-ops).

Prefill (chunked): q [total_tokens, num_q_heads, head_dim] plus
query_start_loc/query_lens describing the ragged token->sequence packing.
The chunk's own K/V are assumed ALREADY written to the pages (paper §4.3:
"Q, K, and V have already been computed before the kernel launch and stored
in the KV cache"). Query row i of sequence s sits at absolute position
  pos = context_lens[s] - query_lens[s] + i
and attends causally over cache positions [0, pos].
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def gather_kv(kv_pages: jax.Array, layer, page_table: jax.Array, d: int):
    """Layer `layer` of [L, Hkv, P, ps, 2D] + [S, Np] -> dense per-seq
    (k, v), each [S, Np*ps, Hkv, D]."""
    g = kv_pages[layer][:, page_table]  # [Hkv, S, Np, ps, 2D]
    hkv, s, np_, ps, w = g.shape
    kv = g.transpose(1, 2, 3, 0, 4).reshape(s, np_ * ps, hkv, w)
    return kv[..., :d], kv[..., d:]


def write_rows_ref(kv_pages: jax.Array, layer, rows: jax.Array,
                   slots: jax.Array) -> jax.Array:
    """Oracle of the in-place write: rows [T, Hkv, W] at flat slots [T]
    (page * page_size + offset) of layer `layer`; slots outside the pool
    drop their row."""
    nl, hkv, p, ps, w = kv_pages.shape
    flat = kv_pages.reshape(nl, hkv, p * ps, w)
    flat = flat.at[layer, :, slots].set(rows, mode="drop")
    return flat.reshape(kv_pages.shape)


def paged_attention_decode_ref(
    q: jax.Array,
    kv_pages: jax.Array,
    layer,
    page_table: jax.Array,
    context_lens: jax.Array,
    *,
    scale: float | None = None,
) -> jax.Array:
    """Oracle for single-token decode over the paged cache."""
    s_, hq, d = q.shape
    hkv = kv_pages.shape[1]
    group = hq // hkv
    if scale is None:
        scale = 1.0 / (d**0.5)
    k, v = gather_kv(kv_pages, layer, page_table, d)  # [S, L, Hkv, D]
    length = k.shape[1]
    qf = q.astype(jnp.float32).reshape(s_, hkv, group, d)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    scores = jnp.einsum("shgd,slhd->shgl", qf, kf) * scale  # [S, Hkv, G, L]
    pos = jnp.arange(length)[None, None, None, :]
    mask = pos < context_lens[:, None, None, None]
    scores = jnp.where(mask, scores, _NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    # fully-masked (dead) rows: softmax gives uniform; zero them explicitly
    p = jnp.where(mask.any(axis=-1, keepdims=True), p, 0.0)
    out = jnp.einsum("shgl,slhd->shgd", p, vf)
    return out.reshape(s_, hq, d).astype(q.dtype)


def paged_attention_prefill_ref(
    q: jax.Array,
    kv_pages: jax.Array,
    layer,
    page_table: jax.Array,
    context_lens: jax.Array,
    query_start_loc: jax.Array,
    query_lens: jax.Array,
    *,
    scale: float | None = None,
) -> jax.Array:
    """Oracle for (chunked-)prefill attention over the paged cache.

    q: [T, Hq, D]; query_start_loc: [S+1]; query_lens: [S].
    Rows outside any live sequence produce zeros.
    """
    t, hq, d = q.shape
    s_ = query_lens.shape[0]
    hkv = kv_pages.shape[1]
    group = hq // hkv
    if scale is None:
        scale = 1.0 / (d**0.5)
    k, v = gather_kv(kv_pages, layer, page_table, d)  # [S, L, Hkv, D]
    length = k.shape[1]

    # map each token row -> (seq idx, abs position); dead rows -> seq 0, pos -1
    rows = jnp.arange(t)
    seq_of_row = jnp.searchsorted(query_start_loc[1:], rows, side="right")
    seq_of_row = jnp.minimum(seq_of_row, s_ - 1)
    in_seq = (rows >= query_start_loc[seq_of_row]) & (
        rows < query_start_loc[seq_of_row] + query_lens[seq_of_row]
    )
    off_in_chunk = rows - query_start_loc[seq_of_row]
    abs_pos = context_lens[seq_of_row] - query_lens[seq_of_row] + off_in_chunk
    abs_pos = jnp.where(in_seq, abs_pos, -1)

    kf = k.astype(jnp.float32)[seq_of_row]  # [T, L, Hkv, D]
    vf = v.astype(jnp.float32)[seq_of_row]
    qf = q.astype(jnp.float32).reshape(t, hkv, group, d)
    scores = jnp.einsum("thgd,tlhd->thgl", qf, kf) * scale
    pos = jnp.arange(length)[None, None, None, :]
    mask = pos <= abs_pos[:, None, None, None]
    scores = jnp.where(mask, scores, _NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    p = jnp.where(mask.any(axis=-1, keepdims=True), p, 0.0)
    out = jnp.einsum("thgl,tlhd->thgd", p, vf)
    return out.reshape(t, hq, d).astype(q.dtype)


def merge_segments_ref(
    o_seg: jax.Array, m_seg: jax.Array, l_seg: jax.Array
) -> jax.Array:
    """Merge per-segment partial attention (paper §4.5 reduction step).

    o_seg: [..., nseg, G, D] UNNORMALIZED accumulators (sum of exp(s-m_s)·V)
    m_seg: [..., nseg, G] per-segment running max
    l_seg: [..., nseg, G] per-segment sum of exponentials
    Returns normalized output [..., G, D]. Dead segments must carry
    m=-inf-like (<= _NEG_INF), l=0, o=0.
    """
    m_star = jnp.max(m_seg, axis=-2, keepdims=True)  # [..., 1, G]
    # all-dead rows: keep zeros
    alive = m_star > _NEG_INF / 2
    m_star_safe = jnp.where(alive, m_star, 0.0)
    w = jnp.exp(m_seg - m_star_safe) * (m_seg > _NEG_INF / 2)  # [..., nseg, G]
    l_tot = jnp.sum(l_seg * w, axis=-2)  # [..., G]
    o_tot = jnp.sum(o_seg * w[..., None], axis=-3)  # [..., G, D]
    l_safe = jnp.where(l_tot == 0.0, 1.0, l_tot)
    return o_tot / l_safe[..., None]
