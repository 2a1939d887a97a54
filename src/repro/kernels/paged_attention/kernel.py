"""Pallas TPU paged-attention kernels — the paper's contribution (§4).

Five stages, mirroring the paper:

  C1 `decode_baseline`   one (sequence × query head) per grid cell; KV tiles
                         streamed through VMEM via scalar-prefetched block
                         tables (paper §4.3 / Listing 3).
  C2 `decode_gqa`        Q-Block packing: all query heads sharing one KV head
                         are processed by one grid cell, so each K/V page is
                         DMA'd once per KV head instead of once per Q head
                         (paper §4.4 / Listing 4). On TPU this also turns the
                         (1×D)·(D×T) GEMV into a (G×D)·(D×T) GEMM that can
                         feed the MXU.
  C3 `decode_segmented`  parallel tiled softmax: the KV sequence is split
                         into segments processed by parallel grid cells, each
                         emitting (acc, max, expsum); `segment_reduce` merges
                         them (paper §4.5 / Listing 5). This is the
                         flash-decoding analog for small-batch long-context.
  C4 adjustable tiles    `tile` decouples the softmax tile from the KV page
                         size (any divisor of page_size; page_size itself may
                         be any multiple of the sublane count, incl.
                         non-power-of-two — paper §4.6's hybrid-model case).
  C5 static launch grid  every grid is sized by compile-time maxima and dead
                         work is masked in-kernel (`context_lens == 0` rows
                         produce exact zeros); combined with XLA's
                         static-shape compilation this is the TPU analog of
                         the paper's CUDA-graph-compatible static grid
                         (paper §4.7 / §6.2).

The prefill kernel (`prefill_qblock`) implements the Q-Block kernel for
chunked prefill over the paged cache, with the paper's §6.1 metadata
(cumulative-Q-block tensor + binary-searched sequence index) computed in
`ops.py` and consumed here through scalar prefetch.

The pool: every kernel reads the whole stacked pool
`kv_pages [L, Hkv, P, ps, Dk+Dv]` (one row per token and KV head, K in its
first Dk lanes and V in the rest) and selects (layer, head, page, tile)
in its index maps, the layer coming in through scalar prefetch; nothing
slices, pads or relayouts a layer in front of them.  `kv_write` updates
the same array in place (`input_output_aliases`), touching only the
16-row blocks that hold the step's new rows.

TPU-tiling notes: K and V share one row so that a 64-wide head still fills
128 lanes.  Where the head dim is a multiple of 128 the kernels split the
row at it; otherwise (`split == 0`) the query is zero-padded across the V
lanes, so q·row == q·k, and P·row holds P·V in its V lanes (the paper's
`tl.dot` padding lesson, §8, without padding the pool).  Q-block row
counts stay a multiple of 8 (fp32 sublanes).
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
    # Mosaic's default contraction rounds float32 operands towards bf16 on
    # a v5e (7e-3 off the float32 reference at Qwen2.5-3B widths); float32
    # pages get float32 math, bf16 pages keep the default
    precision = (jax.lax.Precision.HIGHEST
                 if a.dtype == b.dtype == jnp.float32 else None)
    return jax.lax.dot_general(a, b, dn, precision=precision,
                               preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# Shared online-softmax tile update
# ---------------------------------------------------------------------------


def _flash_tile_update(q, k, v, kv_start, limit, scale, acc_ref, m_ref, l_ref,
                       q_pos=None):
    """One tiled-softmax step (paper §4.1 'Tiled Softmax').

    q: [M, D] fp; k/v: [tile, D]; masks kv positions >= limit and, if q_pos
    given ([M] absolute query positions), kv positions > q_pos (causality).
    acc_ref [M, D], m_ref/l_ref [M, 128] fp32 running state.
    """
    tile = k.shape[0]
    s = _dot(q, k, trans_b=True) * scale  # [M, tile] fp32
    kv_pos = kv_start + jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
    mask = kv_pos < limit
    if q_pos is not None:
        mask = mask & (kv_pos <= q_pos[:, None])
    s = jnp.where(mask, s, _NEG_INF)
    m_prev = m_ref[:, :1]  # [M, 1]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # rows with no valid kv yet keep m at -inf-ish; guard the exp
    m_safe = jnp.where(m_new <= _NEG_INF, 0.0, m_new)
    p = jnp.exp(jnp.where(mask, s - m_safe, _NEG_INF))  # exp(-big)=0 for dead
    alpha = jnp.exp(jnp.minimum(m_prev - m_safe, 0.0))
    alpha = jnp.where(m_prev <= _NEG_INF, 0.0, alpha)
    l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + _dot(p.astype(v.dtype), v)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)


def _split_kv(kv, split: int):
    """One pool row [tile, Dk+Dv] -> (k, v) operands: lane-aligned halves
    where `split` (the head dim) is a multiple of 128, else the whole row
    for both (the caller's zero-padded q and V-lane output do the rest)."""
    if split:
        return kv[:, :split], kv[:, split:]
    return kv, kv


def _init_state(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


# ---------------------------------------------------------------------------
# C1/C2 — decode kernels (baseline & GQA Q-Block)
# ---------------------------------------------------------------------------


def _decode_kernel(
    # scalar prefetch
    layer_ref,  # [1] int32
    page_table_ref,  # [S, Np] int32
    context_lens_ref,  # [S] int32
    # inputs
    q_ref,  # [1, 1, M, Dq]
    kv_ref,  # [1, 1, 1, tile, W]
    # outputs
    o_ref,  # [1, 1, M, D]
    # scratch
    acc_ref,
    m_ref,
    l_ref,
    *,
    tile: int,
    scale: float,
    split: int,
):
    s = pl.program_id(0)
    t = pl.program_id(2)
    ctx = context_lens_ref[s]

    @pl.when(t == 0)
    def _():
        _init_state(acc_ref, m_ref, l_ref)

    @pl.when(t * tile < ctx)
    def _():
        q = q_ref[0, 0].astype(jnp.float32)
        k, v = _split_kv(kv_ref[0, 0, 0], split)
        _flash_tile_update(q, k, v, t * tile, ctx, scale, acc_ref, m_ref, l_ref)

    @pl.when(t == pl.num_programs(2) - 1)
    def _():
        l = l_ref[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def _make_kv_index_map(tile: int, tiles_per_page: int, head_of_cell):
    """Index map streaming KV pages through the block-table indirection.

    Dead tiles are clamped to the last live tile's page so Pallas skips the
    redundant DMA (revisited block indices are not re-fetched) — the TPU
    expression of the paper's 'excess instances exit immediately'.
    """

    def index_map(s, h, t, layer_ref, page_table_ref, context_lens_ref):
        ctx = context_lens_ref[s]
        max_tile = jnp.maximum(jax.lax.div(ctx - 1, jnp.int32(tile)), 0)
        t_eff = jnp.minimum(t, max_tile)
        page = page_table_ref[s, jax.lax.div(t_eff, jnp.int32(tiles_per_page))]
        return (
            layer_ref[0],
            head_of_cell(h),
            page,
            jax.lax.rem(t_eff, jnp.int32(tiles_per_page)),
            0,
        )

    return index_map


def paged_decode(
    q: jax.Array,  # [S, n_cells, M, Dq]  (pre-packed by ops.py)
    kv_pages: jax.Array,  # [L, Hkv, P, ps, W]  the whole stacked pool
    layer: jax.Array,  # [1] int32
    page_table: jax.Array,  # [S, Np]
    context_lens: jax.Array,  # [S]
    *,
    tile: int,
    tiles_per_page: int,
    num_tiles: int,  # static grid extent = Np * tiles_per_page
    kv_head_of_cell,  # cell index -> kv head (identity for GQA variant)
    scale: float,
    split: int,
    interpret: bool = False,
) -> jax.Array:
    """Shared driver for C1 (baseline, n_cells=Hq, M=1) and C2 (GQA,
    n_cells=Hkv, M=group).  Returns [S, n_cells, M, W - split]."""
    s_, n_cells, m, dq = q.shape
    w = kv_pages.shape[-1]
    dv = w - split
    grid = (s_, n_cells, num_tiles)
    kernel = functools.partial(_decode_kernel, tile=tile, scale=scale,
                               split=split)
    kv_spec = pl.BlockSpec(
        (1, 1, 1, tile, w), _make_kv_index_map(tile, tiles_per_page, kv_head_of_cell)
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, m, dq), lambda s, h, t, *_: (s, h, 0, 0)),
                kv_spec,
            ],
            out_specs=pl.BlockSpec(
                (1, 1, m, dv), lambda s, h, t, *_: (s, h, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((m, dv), jnp.float32),
                pltpu.VMEM((m, 128), jnp.float32),
                pltpu.VMEM((m, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((s_, n_cells, m, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="paged_decode",
    )(layer, page_table, context_lens, q, kv_pages)
    return out


# ---------------------------------------------------------------------------
# C3 — segmented decode (parallel tiled softmax) + reduction kernel
# ---------------------------------------------------------------------------


def _decode_segmented_kernel(
    layer_ref,
    page_table_ref,
    context_lens_ref,
    q_ref,  # [1, 1, M, Dq]
    kv_ref,  # [1, 1, 1, tile, W]
    o_ref,  # [1, 1, 1, M, W - split]   (per segment, unnormalized acc)
    m_out_ref,  # [1, 1, 1, M, 128]  (lane-broadcast, like the scratch)
    l_out_ref,  # [1, 1, 1, M, 128]
    acc_ref,
    m_ref,
    l_ref,
    *,
    tile: int,
    tiles_per_segment: int,
    scale: float,
    split: int,
):
    s = pl.program_id(0)
    g = pl.program_id(2)  # segment index
    t = pl.program_id(3)  # tile within segment
    ctx = context_lens_ref[s]
    tile_idx = g * tiles_per_segment + t

    @pl.when(t == 0)
    def _():
        _init_state(acc_ref, m_ref, l_ref)

    @pl.when(tile_idx * tile < ctx)
    def _():
        q = q_ref[0, 0].astype(jnp.float32)
        k, v = _split_kv(kv_ref[0, 0, 0], split)
        _flash_tile_update(
            q, k, v, tile_idx * tile, ctx, scale, acc_ref, m_ref, l_ref
        )

    @pl.when(t == pl.num_programs(3) - 1)
    def _():
        o_ref[0, 0, 0] = acc_ref[...].astype(o_ref.dtype)
        m_out_ref[0, 0, 0] = m_ref[...]
        l_out_ref[0, 0, 0] = l_ref[...]


def _make_seg_kv_index_map(tile, tiles_per_page, tiles_per_segment):
    def index_map(s, h, g, t, layer_ref, page_table_ref, context_lens_ref):
        ctx = context_lens_ref[s]
        max_tile = jnp.maximum(jax.lax.div(ctx - 1, jnp.int32(tile)), 0)
        t_eff = jnp.minimum(g * tiles_per_segment + t, max_tile)
        page = page_table_ref[s, jax.lax.div(t_eff, jnp.int32(tiles_per_page))]
        return (layer_ref[0], h, page,
                jax.lax.rem(t_eff, jnp.int32(tiles_per_page)), 0)

    return index_map


def paged_decode_segmented(
    q: jax.Array,  # [S, Hkv, M, Dq]
    kv_pages: jax.Array,  # [L, Hkv, P, ps, W]
    layer: jax.Array,  # [1] int32
    page_table: jax.Array,
    context_lens: jax.Array,
    *,
    tile: int,
    tiles_per_page: int,
    num_segments: int,
    tiles_per_segment: int,
    scale: float,
    split: int,
    interpret: bool = False,
):
    """Returns (o_seg [S,Hkv,nseg,M,W-split] f32 unnormalized, m_seg,
    l_seg).

    m_seg / l_seg are [S, Hkv, nseg, M, 128], every lane holding the same
    value: Mosaic tiles the last two block dims by (8, 128) unless they
    span the whole array, so a per-segment [M] row (block (1, 1, 1, M)
    over [S, Hkv, nseg, M]) would put a 1 in the second-minor dim."""
    s_, hkv, m, dq = q.shape
    w = kv_pages.shape[-1]
    d = w - split
    grid = (s_, hkv, num_segments, tiles_per_segment)
    kernel = functools.partial(
        _decode_segmented_kernel,
        tile=tile,
        tiles_per_segment=tiles_per_segment,
        scale=scale,
        split=split,
    )
    kv_spec = pl.BlockSpec(
        (1, 1, 1, tile, w),
        _make_seg_kv_index_map(tile, tiles_per_page, tiles_per_segment),
    )
    out_shapes = (
        jax.ShapeDtypeStruct((s_, hkv, num_segments, m, d), jnp.float32),
        jax.ShapeDtypeStruct((s_, hkv, num_segments, m, 128), jnp.float32),
        jax.ShapeDtypeStruct((s_, hkv, num_segments, m, 128), jnp.float32),
    )
    o_seg, m_seg, l_seg = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, m, dq), lambda s, h, g, t, *_: (s, h, 0, 0)),
                kv_spec,
            ],
            out_specs=(
                pl.BlockSpec(
                    (1, 1, 1, m, d), lambda s, h, g, t, *_: (s, h, g, 0, 0)
                ),
                pl.BlockSpec(
                    (1, 1, 1, m, 128), lambda s, h, g, t, *_: (s, h, g, 0, 0)
                ),
                pl.BlockSpec(
                    (1, 1, 1, m, 128), lambda s, h, g, t, *_: (s, h, g, 0, 0)
                ),
            ),
            scratch_shapes=[
                pltpu.VMEM((m, d), jnp.float32),
                pltpu.VMEM((m, 128), jnp.float32),
                pltpu.VMEM((m, 128), jnp.float32),
            ],
        ),
        out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="paged_decode_segmented",
    )(layer, page_table, context_lens, q, kv_pages)
    return o_seg, m_seg, l_seg


def _segment_reduce_kernel(o_seg_ref, m_seg_ref, l_seg_ref, o_ref):
    """Merge segments (paper Listing 5 `reduce_segments`)."""
    o_seg = o_seg_ref[0, 0]  # [nseg, M, D] f32
    m_seg = m_seg_ref[0, 0, :, :, :1]  # [nseg, M, 1] (lane 0)
    l_seg = l_seg_ref[0, 0, :, :, :1]
    m_star = jnp.max(m_seg, axis=0, keepdims=True)  # [1, M, 1]
    alive = m_star > _NEG_INF / 2
    m_safe = jnp.where(alive, m_star, 0.0)
    w = jnp.exp(m_seg - m_safe) * (m_seg > _NEG_INF / 2)  # [nseg, M, 1]
    l_tot = jnp.sum(l_seg * w, axis=0)  # [M, 1]
    o_tot = jnp.sum(o_seg * w, axis=0)  # [M, D]
    l_safe = jnp.where(l_tot == 0.0, 1.0, l_tot)
    o_ref[0, 0] = (o_tot / l_safe).astype(o_ref.dtype)


def segment_reduce(
    o_seg: jax.Array,  # [S, Hkv, nseg, M, D] f32
    m_seg: jax.Array,
    l_seg: jax.Array,
    out_dtype,
    *,
    interpret: bool = False,
) -> jax.Array:
    s_, hkv, nseg, m, d = o_seg.shape
    return pl.pallas_call(
        _segment_reduce_kernel,
        grid=(s_, hkv),
        in_specs=[
            pl.BlockSpec((1, 1, nseg, m, d), lambda s, h: (s, h, 0, 0, 0)),
            pl.BlockSpec((1, 1, nseg, m, 128), lambda s, h: (s, h, 0, 0, 0)),
            pl.BlockSpec((1, 1, nseg, m, 128), lambda s, h: (s, h, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, m, d), lambda s, h: (s, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((s_, hkv, m, d), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
        name="paged_segment_reduce",
    )(o_seg, m_seg, l_seg)


# ---------------------------------------------------------------------------
# C2 (prefill) — Q-Block chunked-prefill kernel over the paged cache
# ---------------------------------------------------------------------------


def _prefill_kernel(
    layer_ref,  # [1] int32
    qb_seq_ref,  # [NQB] int32  sequence of this q block (-1 dead)
    qb_pos0_ref,  # [NQB] int32  absolute position of the block's 1st token
    page_table_ref,  # [S, Np]
    context_lens_ref,  # [S]
    q_ref,  # [1, 1, BM, Dq]   BM = BQ * G, row = tok * G + g
    kv_ref,  # [1, 1, 1, tile, W]
    o_ref,  # [1, 1, BM, W - split]
    acc_ref,
    m_ref,
    l_ref,
    *,
    tile: int,
    block_q: int,
    group: int,
    scale: float,
    split: int,
):
    qb = pl.program_id(0)
    t = pl.program_id(2)
    seq = qb_seq_ref[qb]
    valid = seq >= 0
    seq_c = jnp.maximum(seq, 0)
    pos0 = qb_pos0_ref[qb]
    ctx = context_lens_ref[seq_c]
    # last kv position this block may attend to
    last_pos = jnp.minimum(pos0 + block_q - 1, ctx - 1)

    @pl.when(t == 0)
    def _():
        _init_state(acc_ref, m_ref, l_ref)

    @pl.when(valid & (t * tile <= last_pos))
    def _():
        q = q_ref[0, 0].astype(jnp.float32)  # [BM, Dq]
        k, v = _split_kv(kv_ref[0, 0, 0], split)
        row = jax.lax.broadcasted_iota(jnp.int32, (q.shape[0],), 0)
        q_pos = pos0 + row // group  # absolute position per Q row
        _flash_tile_update(
            q, k, v, t * tile, ctx, scale, acc_ref, m_ref, l_ref, q_pos=q_pos
        )

    @pl.when(t == pl.num_programs(2) - 1)
    def _():
        l = l_ref[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def _make_prefill_kv_index_map(tile, tiles_per_page, block_q):
    def index_map(qb, h, t, layer_ref, qb_seq_ref, qb_pos0_ref,
                  page_table_ref, cl_ref):
        seq = jnp.maximum(qb_seq_ref[qb], 0)
        ctx = cl_ref[seq]
        last_pos = jnp.clip(qb_pos0_ref[qb] + block_q - 1, 0, jnp.maximum(ctx - 1, 0))
        max_tile = jax.lax.div(last_pos, jnp.int32(tile))
        t_eff = jnp.minimum(t, max_tile)
        page = page_table_ref[seq, jax.lax.div(t_eff, jnp.int32(tiles_per_page))]
        return (layer_ref[0], h, page,
                jax.lax.rem(t_eff, jnp.int32(tiles_per_page)), 0)

    return index_map


def paged_prefill_qblock(
    q_packed: jax.Array,  # [NQB, Hkv, BM, Dq]
    kv_pages: jax.Array,  # [L, Hkv, P, ps, W]
    layer: jax.Array,  # [1] int32
    qb_seq: jax.Array,  # [NQB] int32 (-1 = dead block)
    qb_pos0: jax.Array,  # [NQB] int32
    page_table: jax.Array,
    context_lens: jax.Array,
    *,
    tile: int,
    tiles_per_page: int,
    num_kv_tiles: int,
    block_q: int,
    group: int,
    scale: float,
    split: int,
    interpret: bool = False,
) -> jax.Array:
    """Returns [NQB, Hkv, BM, W - split]."""
    nqb, hkv, bm, dq = q_packed.shape
    w = kv_pages.shape[-1]
    d = w - split
    grid = (nqb, hkv, num_kv_tiles)
    kernel = functools.partial(
        _prefill_kernel, tile=tile, block_q=block_q, group=group, scale=scale,
        split=split,
    )
    kv_spec = pl.BlockSpec(
        (1, 1, 1, tile, w),
        _make_prefill_kv_index_map(tile, tiles_per_page, block_q),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=grid,
            in_specs=[
                pl.BlockSpec(
                    (1, 1, bm, dq), lambda qb, h, t, *refs: (qb, h, 0, 0)
                ),
                kv_spec,
            ],
            out_specs=pl.BlockSpec(
                (1, 1, bm, d), lambda qb, h, t, *refs: (qb, h, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((bm, d), jnp.float32),
                pltpu.VMEM((bm, 128), jnp.float32),
                pltpu.VMEM((bm, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((nqb, hkv, bm, d), q_packed.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="paged_prefill_qblock",
    )(layer, qb_seq, qb_pos0, page_table, context_lens, q_packed, kv_pages)


# ---------------------------------------------------------------------------
# In-place pool update: the step's new rows, nothing else
# ---------------------------------------------------------------------------

# Rows per write block: a bf16 (16, 128) tile, two float32 (8, 128) tiles.
WRITE_ROWS = 16


def write_block_rows(page_size: int) -> int:
    """Rows of the blocks `kv_write` rewrites: WRITE_ROWS, or the whole
    page where WRITE_ROWS does not divide it."""
    return WRITE_ROWS if page_size % WRITE_ROWS == 0 else page_size


def _write_kernel(
    # scalar prefetch, [NB] each: pool block of write block b, and where
    # its new rows sit in the staged rows
    layer_ref,  # [1]
    page_ref,  # page of block b
    blk_ref,  # which WRITE_ROWS-row block of that page
    src_ref,  # staged row that lands on the block's row 0
    r0_ref,  # new rows of block b are rows [r0, r1)
    r1_ref,
    rows_ref,  # [Hkv * nc, T + 2 * BR, lanes] f32, staged new rows
    pool_ref,  # [1, Hkv, 1, BR, W]  the block as it is (aliased)
    out_ref,  # [1, Hkv, 1, BR, W]
    *,
    lanes: int,
):
    b = pl.program_id(0)
    hkv, br, w = out_ref.shape[1], out_ref.shape[3], out_ref.shape[4]
    row = jax.lax.broadcasted_iota(jnp.int32, (br, lanes), 0)
    live = (row >= r0_ref[b]) & (row < r1_ref[b])
    src = src_ref[b]
    nc = w // lanes
    for h in range(hkv):
        for c in range(nc):
            cols = pl.ds(c * lanes, lanes)
            # a dynamic sublane offset is a single float32 vreg column
            # load on the TPU; bf16 rows could not be read this way
            new = rows_ref[h * nc + c, pl.ds(src, br), :]
            out_ref[0, h, 0, :, cols] = jnp.where(
                live, new.astype(out_ref.dtype), pool_ref[0, h, 0, :, cols])


def kv_write(
    kv_pages: jax.Array,  # [L, Hkv, P, ps, W]
    rows: jax.Array,  # [Hkv * nc, T + 2 * BR, lanes] float32 (ops.py stages)
    layer: jax.Array,  # [1] int32
    page: jax.Array,  # [NB] int32
    blk: jax.Array,
    src: jax.Array,
    r0: jax.Array,
    r1: jax.Array,
    num_blocks: jax.Array,  # () int32: live write blocks, the grid extent
    *,
    interpret: bool = False,
) -> jax.Array:
    """Write the step's new rows into the pool in place.  The grid walks
    only the `num_blocks` WRITE_ROWS-row blocks that receive a row; each
    step reads its block, merges rows [r0, r1) and writes it back, so a
    step moves O(new rows) bytes whatever the pool's size.  Blocks must be
    distinct: each sequence writes one run of consecutive positions."""
    _, hkv, _, ps, w = kv_pages.shape
    br = write_block_rows(ps)
    lanes = rows.shape[-1]

    def pool_block(b, layer_ref, page_ref, blk_ref, *_):
        return (layer_ref[0], 0, page_ref[b], blk_ref[b], 0)

    spec = pl.BlockSpec((1, hkv, 1, br, w), pool_block)
    return pl.pallas_call(
        functools.partial(_write_kernel, lanes=lanes),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(num_blocks,),
            in_specs=[pl.BlockSpec(rows.shape, lambda b, *_: (0, 0, 0)),
                      spec],
            out_specs=spec,
        ),
        out_shape=jax.ShapeDtypeStruct(kv_pages.shape, kv_pages.dtype),
        input_output_aliases={7: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="paged_kv_write",
    )(layer, page, blk, src, r0, r1, rows, kv_pages)
