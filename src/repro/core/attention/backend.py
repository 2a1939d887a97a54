"""Attention backend dispatch — the vLLM `triton_attn`-backend analog.

Two backends (paper Fig. 1/2 architecture):
  'pallas'  the paper's kernels (native on TPU, interpret mode on CPU).
  'xla'     pure-jnp paged attention (gather + online-softmax scan); the
            backend compiled in the 512-device dry-run and the default for
            CPU-hosted tests of the full serving stack.

Both consume the same paged cache + metadata and produce identical math
(cross-checked in tests/test_unified_attention.py).  The cache is the whole
stacked pool `kv_pages [L, Hkv, G, P, ps, Dk+Dv]` plus the layer to read
(core/paged/kv_cache.py); `write_kv` puts a step's new rows into it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.attention import heuristics
from repro.core.paged.kv_cache import (
    gather_pages, require_single_pool, write_pages,
)
from repro.kernels.flash_attention.ref import flash_attention_xla
from repro.kernels.paged_attention import ops as paged_ops

_NEG = -0.7 * float(jnp.finfo(jnp.float32).max)


def _osm_update(acc, mm, ll, sc, mask, v_blk, pv_spec: str):
    """One masked online-softmax block update (shared by the streaming
    decode and cached-prefill scan paths — the math must stay identical)."""
    sc = jnp.where(mask, sc, _NEG)
    m_new = jnp.maximum(mm, jnp.max(sc, -1))
    m_safe = jnp.where(m_new <= _NEG, 0.0, m_new)
    pp = jnp.where(mask, jnp.exp(sc - m_safe[..., None]), 0.0)
    alpha = jnp.where(mm <= _NEG, 0.0, jnp.exp(mm - m_safe))
    ll = ll * alpha + jnp.sum(pp, -1)
    acc = acc * alpha[..., None] + jnp.einsum(
        pv_spec, pp, v_blk.astype(jnp.float32))
    return acc, m_new, ll


def _osm_finalize(acc, ll):
    ll = jnp.where(ll == 0.0, 1.0, ll)
    return acc / ll[..., None]


def _local_pool(kv_pages: jax.Array, site: str) -> jax.Array:
    """The single-pool view the Pallas kernels read: [L, Hkv, P, ps, W]
    (dropping the size-1 pool axis is free)."""
    require_single_pool(kv_pages, site)
    return kv_pages[:, :, 0]


def _dense_kv(kv_pages, layer, page_table, dk: int, v_dim=None):
    """Per-seq dense (k, v) of one layer, [S, Np*ps, Hkv, D] each.  GQA
    rows split at `dk`; with `v_dim` (MLA) K is the whole latent row and V
    its first `v_dim` lanes."""
    kv = gather_pages(kv_pages, layer, page_table)
    if v_dim is not None:
        return kv, kv[..., :v_dim]
    return kv[..., :dk], kv[..., dk:]


def write_kv(backend: str, kv_pages: jax.Array, layer, rows: jax.Array,
             slots: jax.Array, plan: dict | None) -> jax.Array:
    """The pool with one layer's new rows [B, S, Hkv, W] written at their
    pool-local slots [B, S] (out-of-range slots drop their row).
      pallas  in place: `ops.write_rows` rewrites only the blocks that
              receive a row; `plan` (`ops.write_plan` of the flattened
              slots) is computed once per step and shared by every layer
      xla     an XLA scatter, batched over pools (`plan` unused)"""
    if backend != "pallas":
        return write_pages(kv_pages, layer, rows, slots)
    pool = _local_pool(kv_pages, "write_kv[pallas]")
    out = paged_ops.write_rows(pool, layer, rows.reshape(-1, *rows.shape[2:]),
                               plan)
    return out[:, :, None]


def decode_attention(
    backend: str,
    q: jax.Array,  # [S, Hq, Dk]
    kv_pages: jax.Array,  # [L, Hkv, num_pools, P, ps, Dk+Dv]
    layer,
    page_table: jax.Array,
    context_lens: jax.Array,
    *,
    scale: float | None = None,
    v_dim: int | None = None,  # MLA: V is the latent prefix of K
    kernel_cfg: heuristics.KernelConfig | None = None,
    blockscan: bool = False,
) -> jax.Array:
    """Single-token decode. Returns [S, Hq, Dv]."""
    if backend == "xla":
        q = _align_q_to_kv_shard(q, kv_pages)
    if blockscan and backend == "xla":
        return decode_attention_blockscan(
            q, kv_pages, layer, page_table, context_lens, scale=scale,
            v_dim=v_dim,
        )
    if backend == "pallas":
        assert v_dim is None, "pallas MLA decode uses the xla path"
        pool = _local_pool(kv_pages, "decode_attention[pallas]")
        cfg = heuristics.validate(
            kernel_cfg or heuristics.KernelConfig("gqa"), pool.shape[3])
        return paged_ops.paged_attention_decode(
            q, pool, layer, page_table, context_lens,
            variant=cfg.variant, tile=cfg.tile,
            num_segments=cfg.num_segments, scale=scale,
        )
    # --- xla backend: dense gather + masked online-softmax scan ---
    k, v = _dense_kv(kv_pages, layer, page_table, q.shape[-1], v_dim)
    out = flash_attention_xla(
        q[:, None], k, v, causal=False, scale=scale,
        kv_block=_pick_kv_block(k.shape[1]), kv_len=context_lens,
    )
    return out[:, 0]


def _align_q_to_kv_shard(q: jax.Array, kv_pages: jax.Array) -> jax.Array:
    """§Perf: when the paged KV is head_dim-sharded (few KV heads), force Q
    into the SAME head_dim sharding. Otherwise GSPMD hits an 'involuntary
    full rematerialization' converting every gathered KV block from the
    D-sharded layout to a head-sharded one (replicates the KV per chip per
    block); aligned layouts turn that into a small per-block score psum."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.distributed import sharding as dsh
    mesh = dsh._mesh()
    if mesh is None:
        return q
    model_n = mesh.shape["model"]
    hkv, dk = kv_pages.shape[1], kv_pages.shape[-1]
    if hkv % model_n == 0 or dk % model_n:
        return q  # KV is head-sharded (or unshardable): leave Q alone
    spec = [None] * q.ndim
    spec[-1] = "model"
    return jax.lax.with_sharding_constraint(
        q, NamedSharding(mesh, P(*spec)))


def decode_attention_blockscan(
    q: jax.Array,  # [S, Hq, Dk]
    kv_pages: jax.Array,  # [L, Hkv, pools, P, ps, Dk+Dv]
    layer,
    page_table: jax.Array,  # [S, Np] pool-local
    context_lens: jax.Array,
    *,
    scale: float | None = None,
    v_dim: int | None = None,
) -> jax.Array:
    """Beyond-paper §Perf decode path: page-block gather INSIDE the online-
    softmax scan. The baseline xla path first materializes the whole dense
    KV copy (gather) and then re-reads it in the scan — ~3x the mandatory
    HBM traffic; this variant streams page groups exactly like the Pallas
    kernel's DMA pipeline, so each KV byte is touched once."""
    s_, hq, dk = q.shape
    _, hkv, pools, p_, ps, w = kv_pages.shape
    group = hq // hkv
    if scale is None:
        scale = dk**-0.5
    np_ = page_table.shape[1]
    ppb = max(1, _pick_kv_block(np_ * ps, target=1024, max_blocks=64) // ps)
    nblk = -(-np_ // ppb)
    pad = nblk * ppb - np_
    pt = jnp.pad(page_table.astype(jnp.int32), ((0, 0), (0, pad)))
    pt_b = jnp.moveaxis(pt.reshape(s_, nblk, ppb), 1, 0)  # [nblk, S, ppb]
    qf = q.astype(jnp.float32).reshape(s_, hkv, group, dk)
    dv = v_dim if v_dim is not None else w - dk

    acc0 = jnp.zeros((s_, hkv, group, dv), jnp.float32)
    m0 = jnp.full((s_, hkv, group), _NEG, jnp.float32)
    l0 = jnp.zeros((s_, hkv, group), jnp.float32)

    def step(carry, xs):
        acc, mm, ll = carry
        ptb, blk = xs  # [S, ppb]
        k_blk, v_blk = _dense_kv(kv_pages, layer, ptb, dk, v_dim)
        sc = jnp.einsum("shgd,skhd->shgk", qf,
                        k_blk.astype(jnp.float32)) * scale
        kv_pos = blk * (ppb * ps) + jnp.arange(ppb * ps)
        mask = (kv_pos[None, :] < context_lens[:, None])[:, None, None, :]
        acc, m_new, ll = _osm_update(acc, mm, ll, sc, mask, v_blk,
                                     "shgk,skhd->shgd")
        return (acc, m_new, ll), None

    from repro.kernels.flash_attention import ref as _fref
    (acc, _, ll), _ = jax.lax.scan(
        step, (acc0, m0, l0), (pt_b, jnp.arange(nblk)),
        unroll=True if _fref.UNROLL_SCANS else 1,
    )
    return _osm_finalize(acc, ll).reshape(s_, hq, dv).astype(q.dtype)


def _pick_kv_block(length: int, target: int = 1024,
                   max_blocks: int = 64) -> int:
    """KV scan granularity: ~1k tokens, capped (best-effort) at 64 scan
    steps so the long-context (500k) cells stay compilable when the
    roofline mode unrolls the scan. The result ALWAYS divides `length`;
    the block cap yields rather than break divisibility (e.g. a 65-page
    table scans in 65 steps instead of crashing the reshape)."""
    kv_block = min(target, length)
    while length % kv_block:
        kv_block //= 2
    while length // kv_block > max_blocks and length % (kv_block * 2) == 0:
        kv_block *= 2
    return min(kv_block, length)


def prefill_attention_uniform(
    backend: str,
    q: jax.Array,  # [B, S, Hq, Dk]
    k_new: jax.Array,  # [B, S, Hkv, Dk] (the chunk's keys, already rope'd)
    v_new: jax.Array,  # [B, S, Hkv, Dv]
    query_lens: jax.Array,  # [B] (<= S; ragged-through-padding)
    kv_pages: jax.Array,
    layer,
    page_table: jax.Array,
    context_lens: jax.Array,
    *,
    scale: float | None = None,
    kernel_cfg: heuristics.KernelConfig | None = None,
) -> jax.Array:
    """Uniform-layout prefill over sequences with NO prior context
    (context_lens == query_lens) — a whole fresh prompt or the FIRST chunk
    of a chunked prefill. The chunk KV is in hand, so the xla path attends
    directly over it; the pallas path reads it back from the pages (paper
    §4.3 semantics). Resumed (context>0) prefill goes through
    `prefill_attention_cached` (uniform batch) or
    `prefill_attention_ragged` (token-packed)."""
    b, s, hq, dk = q.shape
    if backend == "pallas":
        pool = _local_pool(kv_pages, "prefill_attention_uniform[pallas]")
        cfg = heuristics.validate(
            kernel_cfg or heuristics.KernelConfig("gqa"), pool.shape[3])
        # uniform padded layout == ragged layout with stride-s starts
        qsl = (jnp.arange(b + 1, dtype=jnp.int32) * s)
        out = paged_ops.paged_attention_prefill(
            q.reshape(b * s, hq, dk), pool, layer,
            page_table, context_lens, qsl, query_lens.astype(jnp.int32),
            block_q=cfg.block_q, tile=cfg.tile, scale=scale,
        )
        return out.reshape(b, s, hq, -1)
    kv_block = min(512, s)
    while s % kv_block:
        kv_block //= 2
    return flash_attention_xla(
        q, k_new, v_new, causal=True, scale=scale, kv_block=kv_block,
        kv_len=query_lens,
    )


def prefill_attention_cached(
    backend: str,
    q: jax.Array,  # [B, S, Hq, Dk] (the uncached suffix chunk, padded)
    query_lens: jax.Array,  # [B] suffix lengths (<= S)
    kv_pages: jax.Array,
    layer,
    page_table: jax.Array,
    context_lens: jax.Array,  # [B] cached + suffix tokens
    *,
    scale: float | None = None,
    kernel_cfg: heuristics.KernelConfig | None = None,
) -> jax.Array:
    """Uniform-layout prefill over sequences WITH prior context
    (context_lens = prior + query_lens) — the shared resume path for BOTH
    prefix-cache hits and chunked-prefill continuations; the prior context
    only has to exist in the pages, not to have been computed this step.
    The chunk's KV is already written to the pages, so BOTH backends read
    the full context back from the pages:
      pallas  the paper's Q-Block ragged kernel via the stride-S trick
              (uniform padded layout == ragged layout with stride-s starts)
      xla     page gather + online-softmax scan with PER-SEQUENCE causal
              offsets (flash_attention_xla only supports a static scalar
              q_offset, and cached lengths vary across the batch)."""
    b, s, hq, dk = q.shape
    if backend == "pallas":
        pool = _local_pool(kv_pages, "prefill_attention_cached[pallas]")
        cfg = heuristics.validate(
            kernel_cfg or heuristics.KernelConfig("gqa"), pool.shape[3])
        qsl = jnp.arange(b + 1, dtype=jnp.int32) * s
        out = paged_ops.paged_attention_prefill(
            q.reshape(b * s, hq, dk), pool, layer,
            page_table, context_lens, qsl, query_lens.astype(jnp.int32),
            block_q=cfg.block_q, tile=cfg.tile, scale=scale,
        )
        return out.reshape(b, s, hq, -1)
    k, v = _dense_kv(kv_pages, layer, page_table, dk)  # [B, Np*ps, Hkv, Dk]
    return _chunked_flash_xla(
        q, k, v, context_lens - query_lens, context_lens, scale=scale,
    )


def _chunked_flash_xla(
    q: jax.Array,  # [B, Sq, Hq, D]
    k: jax.Array,  # [B, Skv, Hkv, D] dense (gathered) context
    v: jax.Array,
    q_start: jax.Array,  # [B] absolute position of each seq's q row 0
    kv_len: jax.Array,  # [B] valid context lengths
    *,
    scale: float | None = None,
) -> jax.Array:
    """Online-softmax flash scan with per-sequence causal offsets: q row j
    of sequence b sits at absolute position q_start[b] + j and attends kv
    positions <= that (and < kv_len[b]). Inference-only (no VJP)."""
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    g = hq // hkv
    if scale is None:
        scale = d**-0.5
    kv_block = _pick_kv_block(skv)
    nkv = skv // kv_block
    qf = q.astype(jnp.float32).reshape(b, sq, hkv, g, d)
    q_pos = q_start[:, None] + jnp.arange(sq)[None, :]  # [B, Sq]
    kb = jnp.moveaxis(k.reshape(b, nkv, kv_block, hkv, d), 1, 0)
    vb = jnp.moveaxis(v.reshape(b, nkv, kv_block, hkv, dv), 1, 0)

    acc0 = jnp.zeros((b, sq, hkv, g, dv), jnp.float32)
    m0 = jnp.full((b, sq, hkv, g), _NEG, jnp.float32)
    l0 = jnp.zeros((b, sq, hkv, g), jnp.float32)

    def step(carry, xs):
        acc, mm, ll = carry
        kc, vc, blk = xs
        sc = jnp.einsum("bqhgd,bkhd->bqhgk", qf,
                        kc.astype(jnp.float32)) * scale
        kv_pos = blk * kv_block + jnp.arange(kv_block)
        mask = (
            (kv_pos[None, None, :] <= q_pos[:, :, None])
            & (kv_pos[None, None, :] < kv_len[:, None, None])
        )[:, :, None, None, :]
        acc, m_new, ll = _osm_update(acc, mm, ll, sc, mask, vc,
                                     "bqhgk,bkhd->bqhgd")
        return (acc, m_new, ll), None

    from repro.kernels.flash_attention import ref as _fref
    (acc, _, ll), _ = jax.lax.scan(
        step, (acc0, m0, l0), (kb, vb, jnp.arange(nkv)),
        unroll=True if _fref.UNROLL_SCANS else 1,
    )
    return _osm_finalize(acc, ll).reshape(b, sq, hq, dv).astype(q.dtype)


# xla ragged path: rows processed per chunk of this many tokens (memory
# bound, not a math knob -- any value gives identical results)
_RAGGED_XLA_ROW_CHUNK = 64

def _ragged_attention_xla(
    q: jax.Array,  # [T, Hq, Dk] token-packed
    kv_pages: jax.Array,
    layer,
    page_table: jax.Array,  # [S, Np]
    context_lens: jax.Array,  # [S]
    query_start_loc: jax.Array,  # [S+1]
    query_lens: jax.Array,  # [S]
    *,
    scale: float | None = None,
) -> jax.Array:
    """xla ragged reference: every packed token is its own 1-row flash
    batch.  Token i of sequence s sits at absolute position
    context_lens[s] - query_lens[s] + (i - query_start_loc[s]) and attends
    that sequence's paged KV up to (and including) itself — the same
    `_osm_update` scan the uniform cached-prefill path runs, so a decode
    row (q == 1) is just a 1-token segment.  The per-token page gather
    densifies each row's KV, so the token axis is processed in
    row-chunks to bound the working set at [chunk, Np*ps] — still the
    CPU reference/test backend (per-token gather beats nothing on
    hardware); the pallas path is the performance path."""
    t = q.shape[0]
    s = query_lens.shape[0]
    require_single_pool(kv_pages, "_ragged_attention_xla")
    tok = jnp.arange(t, dtype=jnp.int32)
    # owning sequence per token (vectorized binary search, paper §6.1);
    # out-of-range (padded) tokens clamp to the last row and mask dead
    # below via kv_len == 0 or the causal bound
    token_seq = jnp.searchsorted(
        query_start_loc[1:], tok, side="right").astype(jnp.int32)
    token_seq = jnp.minimum(token_seq, s - 1)
    q_pos = (context_lens[token_seq] - query_lens[token_seq]
             + (tok - query_start_loc[token_seq]))
    kv_len = context_lens[token_seq]
    # padded tail tokens (past the last live row) would alias the last
    # sequence at positions >= its context: clamp their kv window shut
    live = tok < query_start_loc[s]
    kv_len = jnp.where(live, kv_len, 0)

    # rows are independent 1-token flash batches, so chunking the token
    # axis is EXACT — it only bounds the dense-KV working set (a long
    # packed prompt would otherwise gather its full context once per
    # token, all at once: [T, Np*ps] blows up host memory where the
    # padded path peaked at [B, Np*ps])
    chunk = min(t, _RAGGED_XLA_ROW_CHUNK)
    pad = -t % chunk
    if pad:
        token_seq = jnp.pad(token_seq, (0, pad))  # seq 0's pages, kv 0
        q_pos = jnp.pad(q_pos, (0, pad))
        kv_len = jnp.pad(kv_len, (0, pad))
        q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    nc = (t + pad) // chunk

    def one_chunk(args):
        qc, seq_c, qpos_c, kvlen_c = args
        pt_tok = page_table[seq_c]  # [chunk, Np]
        k, v = _dense_kv(kv_pages, layer, pt_tok, q.shape[-1])
        return _chunked_flash_xla(
            qc[:, None], k, v, qpos_c, kvlen_c, scale=scale,
        )[:, 0]

    out = jax.lax.map(one_chunk, (
        q.reshape(nc, chunk, *q.shape[1:]),
        token_seq.reshape(nc, chunk),
        q_pos.reshape(nc, chunk),
        kv_len.reshape(nc, chunk),
    ))
    return out.reshape(nc * chunk, *out.shape[2:])[:t]


def prefill_attention_ragged(
    backend: str,
    q: jax.Array,  # [T, Hq, Dk] token-packed
    kv_pages: jax.Array,
    layer,
    page_table: jax.Array,
    context_lens: jax.Array,
    query_start_loc: jax.Array,
    query_lens: jax.Array,
    *,
    scale: float | None = None,
    kernel_cfg: heuristics.KernelConfig | None = None,
) -> jax.Array:
    """General ragged chunked prefill (token-packed layout):
      pallas  the paper's Q-Block kernel; KV (incl. the chunk) is read
              from the pages
      xla     per-token gather + online-softmax reference scan
    Decode-mixed packed batches go through `unified_attention`, which
    routes q == 1 rows around the Q-Block machinery."""
    if backend == "xla":
        return _ragged_attention_xla(
            q, kv_pages, layer, page_table, context_lens,
            query_start_loc, query_lens, scale=scale,
        )
    pool = _local_pool(kv_pages, "prefill_attention_ragged[pallas]")
    cfg = heuristics.validate(
        kernel_cfg or heuristics.KernelConfig("gqa"), pool.shape[3])
    return paged_ops.paged_attention_prefill(
        q, pool, layer, page_table, context_lens,
        query_start_loc, query_lens, block_q=cfg.block_q, tile=cfg.tile,
        scale=scale,
    )


def unified_attention(
    backend: str,
    q: jax.Array,  # [T, Hq, Dk] token-packed, decode rows first
    kv_pages: jax.Array,
    layer,
    page_table: jax.Array,  # [S, Np]
    context_lens: jax.Array,  # [S]
    query_start_loc: jax.Array,  # [S+1]
    query_lens: jax.Array,  # [S]
    *,
    num_decode_seqs: int = 0,
    scale: float | None = None,
    kernel_cfg: heuristics.KernelConfig | None = None,
) -> jax.Array:
    """The unified engine-step attention: ONE launch over a token-packed
    batch mixing decode rows (q = 1), fresh prefill chunks (context ==
    query), and resumed/cached chunks (context > query).

    Layout contract (built host-side by the engine): sequences
    [0, num_decode_seqs) are the STATIC decode region — one token row
    each, `query_start_loc[i] == i`, dead slots masked by
    `context_lens == 0` — and the remaining sequences are ragged chunks
    packed behind them.  `num_decode_seqs` is static dispatch metadata
    (like `kernel_cfg`), baked into the traced program.

      pallas  q = 1 rows run the C1-C3 decode kernels (no Q-Block causal
              inner loop), chunk rows the §6.1 Q-Block prefill kernel —
              bit-identical to the per-kind launches this replaces
      xla     the ragged reference scan (a decode row is a 1-token
              segment; same `_osm_update` math as the cached path)
    """
    if backend == "xla":
        return _ragged_attention_xla(
            q, kv_pages, layer, page_table, context_lens,
            query_start_loc, query_lens, scale=scale,
        )
    pool = _local_pool(kv_pages, "unified_attention[pallas]")
    cfg = heuristics.validate(
        kernel_cfg or heuristics.KernelConfig("gqa"), pool.shape[3])
    return paged_ops.paged_attention_unified(
        q, pool, layer, page_table, context_lens,
        query_start_loc, query_lens, num_decode_seqs=num_decode_seqs,
        variant=cfg.variant, tile=cfg.tile, num_segments=cfg.num_segments,
        block_q=cfg.block_q, scale=scale,
    )
