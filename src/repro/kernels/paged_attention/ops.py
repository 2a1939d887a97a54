"""Jitted wrappers around the paged-attention Pallas kernels.

Responsibilities (all static-shaped, jit-friendly):
  * the pool is the whole stacked `kv_pages [L, Hkv, P, ps, 2*D]` (K in
    the first D lanes of a row, V in the rest) plus a `layer` index; the
    kernels read it as it is, tile by tile (C4: `tile` is decoupled from
    the page size and may be any divisor that is a multiple of 8).
  * TPU alignment: a head dim that is not a multiple of 128 lanes gets its
    query zero-padded across the V lanes (see kernel.py); Q-block rows ->
    multiple of 8 sublanes (the paper's `tl.dot` padding lesson, §8).
  * Q packing for the GQA Q-Block layout (C2) and the prefill metadata
    (cumulative-Q-block tensor + vectorized binary search, paper §6.1).
  * variant plumbing: `baseline` / `gqa` / `segmented` (C1/C2/C3).
  * `write_plan` / `write_rows`: the step's new rows into the pool, in
    place.

Interpret mode: `interpret=None` auto-selects True off-TPU so the same call
sites run on CPU (tests) and TPU (deployment).
"""
from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp

from repro import kernels
from repro.core.paged.kv_cache import ShardingError
from repro.kernels.paged_attention import kernel as K
from repro.utils.misc import cdiv

LANE = 128
SUBLANE = 8


def _pool_query(q: jax.Array, kv_pages: jax.Array) -> tuple[jax.Array, int]:
    """(q as the kernels take it, split): a head dim that is a multiple of
    128 lanes splits the pool row at D; any other is read as the whole
    row, so q is zero-padded across the V lanes (q·row == q·k)."""
    d, w = q.shape[-1], kv_pages.shape[-1]
    if w != 2 * d:
        raise ValueError(f"pool rows hold {w} lanes, expected K and V of "
                         f"head_dim {d} ({2 * d})")
    if d % LANE == 0:
        return q, d
    pad = [(0, 0)] * (q.ndim - 1) + [(0, w - d)]
    return jnp.pad(q, pad), 0


def _layer(layer) -> jax.Array:
    return jnp.asarray(layer, jnp.int32).reshape(1)


def default_tile(page_size: int) -> int:
    """Largest multiple-of-8 tile <= min(page_size, 512) dividing page_size."""
    for t in (512, 256, 128, 64, 32, 24, 16, 8):
        if t <= page_size and page_size % t == 0:
            return t
    return page_size


@functools.partial(
    jax.jit,
    static_argnames=(
        "variant",
        "tile",
        "num_segments",
        "scale",
        "interpret",
    ),
)
def paged_attention_decode(
    q: jax.Array,  # [S, Hq, D]
    kv_pages: jax.Array,  # [L, Hkv, P, ps, 2*D]
    layer: jax.Array,  # () int32
    page_table: jax.Array,  # [S, Np] int32
    context_lens: jax.Array,  # [S] int32
    *,
    variant: Literal["baseline", "gqa", "segmented"] = "gqa",
    tile: int | None = None,
    num_segments: int = 8,
    scale: float | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Single-token decode over the paged KV cache. Returns [S, Hq, D]."""
    interpret = kernels.interpret_mode(interpret)
    s_, hq, d = q.shape
    _, hkv, p, ps, w = kv_pages.shape
    group = hq // hkv
    if scale is None:
        scale = 1.0 / (d**0.5)
    if tile is None:
        tile = default_tile(ps)
    assert ps % tile == 0, f"tile {tile} must divide page_size {ps}"
    q, split = _pool_query(q, kv_pages)
    dq, dv = q.shape[-1], w - split
    tpp = ps // tile
    np_ = page_table.shape[1]
    num_tiles = np_ * tpp
    layer = _layer(layer)
    page_table = page_table.astype(jnp.int32)
    context_lens = context_lens.astype(jnp.int32)
    kw = dict(tile=tile, tiles_per_page=tpp, scale=scale, split=split,
              interpret=interpret)

    if variant == "baseline":
        # paper §4.3: one (seq, q_head) per cell; each q head re-streams KV.
        qq = q.reshape(s_, hq, 1, dq)
        out = K.paged_decode(
            qq, kv_pages, layer, page_table, context_lens,
            num_tiles=num_tiles,
            kv_head_of_cell=lambda h: jax.lax.div(h, jnp.int32(group)), **kw,
        )
    elif variant == "gqa":
        # paper §4.4: Q-Block = all q heads sharing a KV head.
        qq = q.reshape(s_, hkv, group, dq)
        out = K.paged_decode(
            qq, kv_pages, layer, page_table, context_lens,
            num_tiles=num_tiles, kv_head_of_cell=lambda h: h, **kw,
        )
    elif variant == "segmented":
        # paper §4.5: parallel tiled softmax + reduction kernel.
        nseg = min(num_segments, num_tiles)
        tps = cdiv(num_tiles, nseg)
        qq = q.reshape(s_, hkv, group, dq)
        o_seg, m_seg, l_seg = K.paged_decode_segmented(
            qq, kv_pages, layer, page_table, context_lens,
            num_segments=nseg, tiles_per_segment=tps, **kw,
        )
        out = K.segment_reduce(o_seg, m_seg, l_seg, q.dtype, interpret=interpret)
    else:
        raise ValueError(f"unknown variant {variant!r}")
    return out.reshape(s_, hq, dv)[..., dv - d:]


# ---------------------------------------------------------------------------
# Prefill: §6.1 metadata + Q packing + kernel call
# ---------------------------------------------------------------------------


def build_qblock_metadata(
    query_start_loc: jax.Array,  # [S+1] int32
    query_lens: jax.Array,  # [S] int32
    context_lens: jax.Array,  # [S] int32
    *,
    block_q: int,
    num_q_blocks: int,  # static maximum
):
    """The paper's §6.1 attention metadata: a cumulative-number-of-Q-Blocks
    tensor and, per Q block, the owning sequence (vectorized binary search —
    `find_seq_idx` in Listings 3-5) and the block's first-token absolute
    position. Dead blocks get seq = -1."""
    nqb_per_seq = cdiv_arr(query_lens, block_q)
    cu_qb = jnp.cumsum(nqb_per_seq)  # [S]
    qb = jnp.arange(num_q_blocks, dtype=jnp.int32)
    seq = jnp.searchsorted(cu_qb, qb, side="right").astype(jnp.int32)
    valid = qb < cu_qb[-1]
    seq_c = jnp.minimum(seq, query_lens.shape[0] - 1)
    qb_off = qb - jnp.where(seq_c > 0, cu_qb[seq_c - 1], 0)
    pos0 = context_lens[seq_c] - query_lens[seq_c] + qb_off * block_q
    qb_seq = jnp.where(valid, seq_c, -1)
    qb_pos0 = jnp.where(valid, pos0, 0)
    # global q-row index of the block's first token
    qb_row0 = jnp.where(valid, query_start_loc[seq_c] + qb_off * block_q, 0)
    # rows actually live in this block (tail blocks may be ragged)
    qb_rows = jnp.where(
        valid,
        jnp.clip(query_lens[seq_c] - qb_off * block_q, 0, block_q),
        0,
    )
    return qb_seq, qb_pos0, qb_row0, qb_rows


def cdiv_arr(a: jax.Array, b: int) -> jax.Array:
    return -(-a // b)


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_decode_seqs",
        "variant",
        "tile",
        "num_segments",
        "block_q",
        "num_q_blocks",
        "scale",
        "interpret",
    ),
)
def paged_attention_unified(
    q: jax.Array,  # [T, Hq, D] token-packed: decode rows first, then chunks
    kv_pages: jax.Array,  # [L, Hkv, P, ps, 2*D]
    layer: jax.Array,  # () int32
    page_table: jax.Array,  # [S, Np]
    context_lens: jax.Array,  # [S]
    query_start_loc: jax.Array,  # [S+1]
    query_lens: jax.Array,  # [S]
    *,
    num_decode_seqs: int = 0,
    variant: Literal["baseline", "gqa", "segmented"] = "gqa",
    tile: int | None = None,
    num_segments: int = 8,
    block_q: int = 16,
    num_q_blocks: int | None = None,
    scale: float | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """One token-packed ragged launch for the whole engine step: decode
    rows (q == 1), fresh prefill chunks, and resumed/cached chunks share a
    single [T, Hq, D] token stream described by
    `query_start_loc`/`query_lens`/`context_lens`.

    The caller lays out the first `num_decode_seqs` sequences as the
    decode region — exactly one token row per sequence, i.e.
    `query_start_loc[i] == i` for i <= num_decode_seqs (dead slots carry
    `context_lens == 0` and produce exact zeros, C5).  That region is
    STATIC, so the q == 1 rows dispatch through `paged_decode`'s
    (S, Hkv)-cell grid — no Q-Block packing, no causal inner-loop masking,
    `group` live MXU rows per cell instead of 1-in-`block_q` — while the
    remaining rows run the §6.1 Q-Block prefill kernel.  Both regions
    reuse the existing kernels unchanged, so outputs are bit-identical to
    the separate decode/prefill launches they replace.
    """
    nd = num_decode_seqs
    t = q.shape[0]
    if nd > t or nd > query_lens.shape[0]:
        raise ShardingError(
            f"paged_attention_unified: decode region ({nd} rows) exceeds "
            f"the packed batch (q shape {tuple(q.shape)}, "
            f"S={query_lens.shape[0]})")
    parts = []
    if nd:
        parts.append(paged_attention_decode(
            q[:nd], kv_pages, layer, page_table[:nd], context_lens[:nd],
            variant=variant, tile=tile, num_segments=num_segments,
            scale=scale, interpret=interpret,
        ))
    if t > nd:
        parts.append(paged_attention_prefill(
            q[nd:], kv_pages, layer, page_table[nd:], context_lens[nd:],
            query_start_loc[nd:] - nd, query_lens[nd:],
            block_q=block_q, tile=tile, num_q_blocks=num_q_blocks,
            scale=scale, interpret=interpret,
        ))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


@functools.partial(
    jax.jit,
    static_argnames=("block_q", "tile", "num_q_blocks", "scale", "interpret"),
)
def paged_attention_prefill(
    q: jax.Array,  # [T, Hq, D]
    kv_pages: jax.Array,  # [L, Hkv, P, ps, 2*D]
    layer: jax.Array,  # () int32
    page_table: jax.Array,  # [S, Np]
    context_lens: jax.Array,  # [S]
    query_start_loc: jax.Array,  # [S+1]
    query_lens: jax.Array,  # [S]
    *,
    block_q: int = 16,
    tile: int | None = None,
    num_q_blocks: int | None = None,  # static; default T//block_q + S
    scale: float | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Chunked-prefill attention over the paged cache (Q-Block kernel, C2).

    The chunk's K/V must already be written to the pages. Returns [T, Hq, D]
    with zeros in dead rows.
    """
    interpret = kernels.interpret_mode(interpret)
    t, hq, orig_d = q.shape
    s_ = query_lens.shape[0]
    _, hkv, p, ps, w = kv_pages.shape
    group = hq // hkv
    if scale is None:
        scale = 1.0 / (orig_d**0.5)
    if tile is None:
        tile = default_tile(ps)
    assert ps % tile == 0, f"tile {tile} must divide page_size {ps}"
    if num_q_blocks is None:
        num_q_blocks = t // block_q + s_
    q, split = _pool_query(q, kv_pages)
    d, dv = q.shape[-1], w - split
    tpp = ps // tile
    np_ = page_table.shape[1]
    num_kv_tiles = np_ * tpp
    page_table = page_table.astype(jnp.int32)
    context_lens = context_lens.astype(jnp.int32)
    query_start_loc = query_start_loc.astype(jnp.int32)
    query_lens = query_lens.astype(jnp.int32)

    qb_seq, qb_pos0, qb_row0, qb_rows = build_qblock_metadata(
        query_start_loc, query_lens, context_lens,
        block_q=block_q, num_q_blocks=num_q_blocks,
    )

    # ---- pack Q into [NQB, Hkv, BM, D], row = tok*group + g ----
    tok = jnp.arange(block_q, dtype=jnp.int32)
    rows = qb_row0[:, None] + tok[None, :]  # [NQB, BQ]
    row_live = tok[None, :] < qb_rows[:, None]
    rows_safe = jnp.where(row_live, jnp.minimum(rows, t - 1), 0)
    qg = q.reshape(t, hkv, group, d)
    q_packed = qg[rows_safe]  # [NQB, BQ, Hkv, G, D]
    q_packed = jnp.where(row_live[:, :, None, None, None], q_packed, 0)
    bm = block_q * group
    q_packed = q_packed.transpose(0, 2, 1, 3, 4).reshape(
        num_q_blocks, hkv, bm, d
    )

    o_packed = K.paged_prefill_qblock(
        q_packed, kv_pages, _layer(layer),
        qb_seq, qb_pos0, page_table, context_lens,
        tile=tile, tiles_per_page=tpp, num_kv_tiles=num_kv_tiles,
        block_q=block_q, group=group, scale=scale, split=split,
        interpret=interpret,
    )

    # ---- scatter back to [T, Hq, D]; dead rows -> dropped ----
    o_packed = o_packed[..., dv - orig_d:].reshape(
        num_q_blocks, hkv, block_q, group, orig_d)
    o_packed = o_packed.transpose(0, 2, 1, 3, 4)  # [NQB, BQ, Hkv, G, D]
    scatter_rows = jnp.where(row_live, rows, t)  # OOB -> dropped
    out = jnp.zeros((t + 1, hkv, group, orig_d), q.dtype)
    out = out.at[scatter_rows.reshape(-1)].set(
        o_packed.reshape(num_q_blocks * block_q, hkv, group, orig_d),
        mode="drop"
    )
    return out[:t].reshape(t, hq, orig_d)


# ---------------------------------------------------------------------------
# In-place pool update
# ---------------------------------------------------------------------------


def write_plan(slots: jax.Array, page_size: int, num_pages: int) -> dict:
    """Where a step's new rows go, computed once per step and shared by
    every layer's `write_rows`.

    slots [T]: each row's pool slot (page * page_size + offset); a slot
    outside the pool (the trash slot) drops its row.  Rows of one write
    block must be adjacent in `slots` and fill consecutive slots: each
    sequence writes one run of positions, which every packed, padded and
    speculative layout does."""
    t = slots.shape[0]
    br = K.write_block_rows(page_size)
    slots = slots.astype(jnp.int32)
    live = (slots >= 0) & (slots < num_pages * page_size)
    blk = jnp.where(live, slots // br, -1)
    prev = jnp.concatenate([jnp.full((1,), -1, jnp.int32), blk[:-1]])
    first = live & (blk != prev)
    (start,) = jnp.nonzero(first, size=t, fill_value=0)
    start = start.astype(jnp.int32)
    block_of_row = jnp.where(live, jnp.cumsum(first) - 1, t)
    count = jax.ops.segment_sum(live.astype(jnp.int32), block_of_row,
                                num_segments=t + 1)[:t]
    s0 = slots[start]
    r0 = s0 % br
    return {
        "page": s0 // page_size,
        "blk": (s0 % page_size) // br,
        "src": start - r0 + br,  # staged rows carry br rows in front
        "r0": r0,
        "r1": r0 + count,
        "num_blocks": jnp.sum(first).astype(jnp.int32),
    }


@functools.partial(jax.jit, static_argnames=("interpret",))
def write_rows(
    kv_pages: jax.Array,  # [L, Hkv, P, ps, W]
    layer: jax.Array,  # () int32
    rows: jax.Array,  # [T, Hkv, W] the step's new rows
    plan: dict,  # write_plan(slots) for the same T rows
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """`kv_pages` with the step's rows written at their slots, in place:
    only the write blocks that receive a row are read and rewritten."""
    interpret = kernels.interpret_mode(interpret)
    t, hkv, w = rows.shape
    br = K.write_block_rows(kv_pages.shape[3])
    lanes = LANE if w % LANE == 0 else w
    nc = w // lanes
    staged = rows.astype(jnp.float32).reshape(t, hkv, nc, lanes)
    staged = staged.transpose(1, 2, 0, 3).reshape(hkv * nc, t, lanes)
    staged = jnp.pad(staged, ((0, 0), (br, br), (0, 0)))
    return K.kv_write(
        kv_pages, staged, _layer(layer), plan["page"], plan["blk"],
        plan["src"], plan["r0"], plan["r1"], plan["num_blocks"],
        interpret=interpret)
