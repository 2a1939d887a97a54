"""Attention layer (GQA / MQA / MHA / MLA) with train / prefill / decode
modes over the paged-KV runtime.

Modes:
  train    full causal flash over the dense sequence (no cache)
  prefill  uniform [B, S] layout; writes the chunk's KV into the pages, then
           attends (backend-dispatched)
  decode   [B, 1]; writes one slot per live sequence, then runs the paper's
           paged decode kernel (or the xla gather backend)
  unified  token-packed [1, T] layout mixing decode rows and ragged
           prefill chunks; per-token slot_mapping writes + one ragged
           launch (the paper's unified-kernel serving path)

MLA (deepseek-v2) caches ONLY the compressed latent+rope vector per token
(576 dims vs 128 heads × 256) and decodes in the absorbed form: all 128
query heads share the single latent 'KV head' — the extreme case of the
paper's §4.4 Q-Block GQA packing.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.attention import backend as attn_backend
from repro.core.paged.kv_cache import (
    ShardingError, physical_slots, write_pages,
)
from repro.distributed.sharding import constrain
from repro.kernels.flash_attention.ref import flash_attention_xla
from repro.kernels.paged_attention import ops as paged_ops
from repro.models import layers as L


def _rope(cfg: ModelConfig, x, positions, rotary_dim=None):
    if cfg.rope_style == "rope":
        return L.apply_rope(x, positions, cfg.rope_theta, rotary_dim)
    if cfg.rope_style == "mrope":
        return L.apply_mrope(x, positions, cfg.rope_theta, cfg.mrope_sections)
    return x


# ---------------------------------------------------------------------------
# standard GQA attention
# ---------------------------------------------------------------------------


def init_attention(cfg: ModelConfig, key):
    if cfg.mla.kv_lora_rank:
        return _init_mla(cfg, key)
    dh = cfg.resolved_head_dim
    dt = cfg.param_dtype
    k1, k2, k3, k4 = jax.random.split(key, 4)
    if cfg.fused_qkv:
        # one column-parallel matmul feeds q|k|v: a single activation
        # all-gather per block instead of three (§Perf fused-qkv iteration)
        return {
            "wqkv": L.init_linear(
                k1, cfg.d_model,
                (cfg.num_q_heads + 2 * cfg.num_kv_heads) * dh,
                bias=cfg.qkv_bias, dtype=dt),
            "wo": L.init_linear(k4, cfg.num_q_heads * dh, cfg.d_model,
                                dtype=dt),
        }
    return {
        "wq": L.init_linear(k1, cfg.d_model, cfg.num_q_heads * dh,
                            bias=cfg.qkv_bias, dtype=dt),
        "wk": L.init_linear(k2, cfg.d_model, cfg.num_kv_heads * dh,
                            bias=cfg.qkv_bias, dtype=dt),
        "wv": L.init_linear(k3, cfg.d_model, cfg.num_kv_heads * dh,
                            bias=cfg.qkv_bias, dtype=dt),
        "wo": L.init_linear(k4, cfg.num_q_heads * dh, cfg.d_model, dtype=dt),
    }


def _qkv(cfg: ModelConfig, p, x, positions):
    b, s, _ = x.shape
    dh = cfg.resolved_head_dim
    if "wqkv" in p:
        hq, hkv = cfg.num_q_heads, cfg.num_kv_heads
        qkv = L.linear(p["wqkv"], x)
        q = qkv[..., : hq * dh].reshape(b, s, hq, dh)
        k = qkv[..., hq * dh : (hq + hkv) * dh].reshape(b, s, hkv, dh)
        v = qkv[..., (hq + hkv) * dh :].reshape(b, s, hkv, dh)
    else:
        hq, hkv = cfg.num_q_heads, cfg.num_kv_heads
        q = L.linear(p["wq"], x).reshape(b, s, hq, dh)
        k = L.linear(p["wk"], x).reshape(b, s, hkv, dh)
        v = L.linear(p["wv"], x).reshape(b, s, hkv, dh)
    q = _rope(cfg, q, positions)
    k = _rope(cfg, k, positions)
    q = constrain(q, "batch", "seq", "heads", "head_dim")
    k = constrain(k, "batch", "seq", "kv_heads", "head_dim")
    v = constrain(v, "batch", "seq", "kv_heads", "head_dim")
    return q, k, v


def kv_write_meta(cfg: ModelConfig, meta: dict, positions, *, mode: str,
                  pool: jax.Array, backend: str) -> dict:
    """`meta` plus where this step's KV rows land, computed once per step
    and shared by every layer: `slots` [B, S] pool-local flat slots (the
    trash slot for rows that write nothing) and, on the pallas backend,
    the in-place write plan over them."""
    pt = meta["page_table"]
    num_pages, ps = pool.shape[3], pool.shape[4]
    if mode == "unified":
        slots = meta["slot_mapping"]
    else:
        pos_abs = positions if positions.ndim == 2 else positions[0]
        if mode == "decode":
            valid = (pos_abs >= 0) & (meta["context_lens"][:, None] > 0)
        else:
            s = pos_abs.shape[1]
            valid = jnp.arange(s)[None, :] < meta["query_lens"][:, None]
        slots = physical_slots(pt, pos_abs, valid, ps, num_pages)
    meta = dict(meta, slots=slots)
    if backend == "pallas" and not cfg.mla.kv_lora_rank:
        meta["write_plan"] = paged_ops.write_plan(slots.reshape(-1), ps,
                                                  num_pages)
    return meta


def _local_heads(x, n_local: int, axis_name: str):
    """This device's contiguous head block of a [B, S, H, D] projection:
    block `axis_index` of the full head set every device projects (the
    serving params are replicated).  RoPE is per-head/position-based, so
    slice-after-rope == rope-after-slice."""
    idx = jax.lax.axis_index(axis_name)
    return jax.lax.dynamic_slice_in_dim(x, idx * n_local, n_local, axis=2)


def attention(cfg: ModelConfig, p, x, positions, *, mode: str,
              cache=None, layer=None, meta=None, backend: str = "xla",
              kernel_cfg=None, shard=None):
    """x [B, S, d]. Returns (out [B, S, d], new_cache_or_None).

    cache: {'kv_pages': [L, Hkv, G, P, ps, Dk+Dv]}, the whole stacked
    pool; `layer` (int or traced scalar) is the one this call writes and
    reads.  The returned cache is the same pool with this layer's rows
    written (in place on the pallas backend).
    meta:  {'page_table', 'context_lens', 'query_lens', ...} (serve modes)
    with `kv_write_meta`'s slots.
    kernel_cfg: static heuristics.KernelConfig chosen at dispatch time
    (None -> the backend's default); selects the paged-kernel variant /
    tile / segments, so it must be part of the engine's executable key.
    shard: static ShardCtx when running per-device inside the mesh
    executor's shard_map (docs/serving.md): q/k/v and the KV pages carry
    only `H/tp` local heads, and ONE all-gather over `shard.axis`
    reassembles the full head set before the replicated `wo`.
    """
    if cfg.mla.kv_lora_rank:
        if shard is not None and shard.size > 1:
            raise ShardingError(
                "MLA attention has a single latent KV head and cannot be "
                f"head-sharded (requested tp={shard.size})")
        return _mla_attention(cfg, p, x, positions, mode=mode, cache=cache,
                              layer=layer, meta=meta, backend=backend)
    b, s, _ = x.shape
    dh = cfg.resolved_head_dim
    q, k, v = _qkv(cfg, p, x, positions)
    if shard is not None and shard.size > 1:
        if mode != "unified":
            raise ShardingError(
                f"the mesh executor only runs the packed unified step; "
                f"attention mode={mode!r} cannot run under tp={shard.size}")
        q = _local_heads(q, cfg.num_q_heads // shard.size, shard.axis)
        k = _local_heads(k, cfg.num_kv_heads // shard.size, shard.axis)
        v = _local_heads(v, cfg.num_kv_heads // shard.size, shard.axis)
    scale = dh**-0.5

    if mode == "train":
        kv_block = min(1024, s)
        while s % kv_block:
            kv_block //= 2
        o = flash_attention_xla(q, k, v, causal=True, scale=scale,
                                kv_block=kv_block)
        new_cache = None
    else:
        pt = meta["page_table"]
        ctx = meta["context_lens"]
        # this layer's new K|V rows land at their slots (trash slot for
        # padded tokens) before the attention reads the pool
        pool = attn_backend.write_kv(
            backend, cache["kv_pages"], layer,
            jnp.concatenate([k, v], axis=-1), meta["slots"],
            meta.get("write_plan"))
        if mode == "unified":
            # token-packed step: x is [1, T, d] with per-token absolute
            # positions (already rope'd above); ONE ragged launch covers
            # decode rows + every chunk.
            o = attn_backend.unified_attention(
                backend, q[0], pool, layer, pt, ctx,
                meta["query_start_loc"], meta["query_lens"],
                num_decode_seqs=meta["num_decode_seqs"], scale=scale,
                kernel_cfg=kernel_cfg,
            )[None]
            if shard is not None and shard.size > 1:
                # the ONE per-step collective: devices hold disjoint
                # contiguous head blocks, so a tiled all-gather on the
                # head axis reassembles exactly the single-device o
                o = jax.lax.all_gather(o, shard.axis, axis=2, tiled=True)
        elif mode == "prefill_cached":
            # prefix-cache resume: positions are offset by the cached
            # context (context_lens = cached + chunk); attend over the
            # pages, which hold the shared prefix + the chunk just
            # written above.
            o = attn_backend.prefill_attention_cached(
                backend, q, meta["query_lens"], pool, layer, pt, ctx,
                scale=scale, kernel_cfg=kernel_cfg,
            )
        elif mode == "prefill":
            o = attn_backend.prefill_attention_uniform(
                backend, q, k, v, meta["query_lens"], pool, layer, pt, ctx,
                scale=scale, kernel_cfg=kernel_cfg,
            )
        elif mode == "decode":
            o = attn_backend.decode_attention(
                backend, q[:, 0], pool, layer, pt, ctx, scale=scale,
                kernel_cfg=kernel_cfg, blockscan=cfg.decode_blockscan,
            )[:, None]
        else:
            raise ValueError(mode)
        new_cache = {"kv_pages": pool}

    o = constrain(o, "batch", "seq", "heads", "head_dim")
    out = L.linear(p["wo"], o.reshape(b, s, -1).astype(x.dtype))
    return constrain(out, "batch", "seq_sp", "embed"), new_cache


# ---------------------------------------------------------------------------
# MLA (deepseek-v2)
# ---------------------------------------------------------------------------


def _init_mla(cfg: ModelConfig, key):
    m = cfg.mla
    dt = cfg.param_dtype
    h = cfg.num_q_heads
    ks = jax.random.split(key, 7)
    p = {
        "wkv_a": L.init_linear(ks[0], cfg.d_model,
                               m.kv_lora_rank + m.qk_rope_dim, dtype=dt),
        "kv_norm": L.init_rms_norm(m.kv_lora_rank, dt),
        "wk_b": L.init_linear(ks[1], m.kv_lora_rank, h * m.qk_nope_dim, dtype=dt),
        "wv_b": L.init_linear(ks[2], m.kv_lora_rank, h * m.v_head_dim, dtype=dt),
        "wo": L.init_linear(ks[3], h * m.v_head_dim, cfg.d_model, dtype=dt),
    }
    if m.q_lora_rank:
        p["wq_a"] = L.init_linear(ks[4], cfg.d_model, m.q_lora_rank, dtype=dt)
        p["q_norm"] = L.init_rms_norm(m.q_lora_rank, dt)
        p["wq_b"] = L.init_linear(
            ks[5], m.q_lora_rank, h * (m.qk_nope_dim + m.qk_rope_dim), dtype=dt
        )
    else:
        p["wq"] = L.init_linear(
            ks[6], cfg.d_model, h * (m.qk_nope_dim + m.qk_rope_dim), dtype=dt
        )
    return p


def _mla_q(cfg, p, x, positions):
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.num_q_heads
    if m.q_lora_rank:
        ql = L.rms_norm(p["q_norm"], L.linear(p["wq_a"], x), cfg.norm_eps)
        q = L.linear(p["wq_b"], ql)
    else:
        q = L.linear(p["wq"], x)
    q = q.reshape(b, s, h, m.qk_nope_dim + m.qk_rope_dim)
    q_nope, q_rope = q[..., : m.qk_nope_dim], q[..., m.qk_nope_dim :]
    q_rope = _rope(cfg, q_rope, positions)
    return q_nope, q_rope


def _mla_latent(cfg, p, x, positions):
    """Compressed KV: (latent [B,S,r], k_rope [B,S,rope]) — what gets cached."""
    m = cfg.mla
    kv = L.linear(p["wkv_a"], x)
    latent = L.rms_norm(p["kv_norm"], kv[..., : m.kv_lora_rank], cfg.norm_eps)
    k_rope = kv[..., m.kv_lora_rank :][:, :, None, :]  # [B,S,1,rope]
    k_rope = _rope(cfg, k_rope, positions)[:, :, 0]
    return latent, k_rope


def _mla_prefill_fused(cfg, p, q_nope, q_rope, latent, k_rope, qlens, *,
                       scale, kv_block=1024, q_chunk=2048):
    """Prefill attention with the per-head K/V EXPANDED INSIDE the KV-block
    scan, processing Q in chunks (beyond-paper §Perf: the naive path
    materializes the full [B,S,H,D] expansion — ~200 GiB/device on
    deepseek-v2 prefill_32k; unchunked Q keeps ~34 GiB fp32 score buffers
    live with 128 heads)."""
    m = cfg.mla
    b, s, h = q_nope.shape[0], q_nope.shape[1], cfg.num_q_heads
    wkb = p["wk_b"]["w"].reshape(m.kv_lora_rank, h, m.qk_nope_dim)
    wvb = p["wv_b"]["w"].reshape(m.kv_lora_rank, h, m.v_head_dim)
    from repro.kernels.flash_attention import ref as _fref
    nkv = s // kv_block
    while s % kv_block:
        kv_block //= 2
        nkv = s // kv_block
    if _fref.UNROLL_SCANS:
        q_chunk = s  # roofline accounting mode: no outer map, unrolled scan
    q_chunk = min(q_chunk, s)
    while s % q_chunk:
        q_chunk //= 2
    ncq = s // q_chunk
    qf = jnp.concatenate([q_nope, q_rope], -1).astype(jnp.float32)
    qc = jnp.moveaxis(qf.reshape(b, ncq, q_chunk, h, -1), 1, 0)
    lat_b = jnp.moveaxis(latent.reshape(b, nkv, kv_block, -1), 1, 0)
    rope_b = jnp.moveaxis(k_rope.reshape(b, nkv, kv_block, -1), 1, 0)
    neg = -0.7 * float(jnp.finfo(jnp.float32).max)

    def one_chunk(args):
        qx, ci = args  # [B, cq, H, D], chunk index
        q_pos = ci * q_chunk + jnp.arange(q_chunk)
        acc0 = jnp.zeros((b, q_chunk, h, m.v_head_dim), jnp.float32)
        m0 = jnp.full((b, q_chunk, h), neg, jnp.float32)
        l0 = jnp.zeros((b, q_chunk, h), jnp.float32)

        def step(carry, xs):
            acc, mm, ll = carry
            lat, rope, blk = xs
            latf = lat.astype(jnp.float32)
            k_nope = jnp.einsum("bkr,rhn->bkhn", latf,
                                wkb.astype(jnp.float32))
            v_blk = jnp.einsum("bkr,rhv->bkhv", latf,
                               wvb.astype(jnp.float32))
            k_blk = jnp.concatenate([
                k_nope,
                jnp.broadcast_to(rope.astype(jnp.float32)[:, :, None, :],
                                 k_nope.shape[:3] + (m.qk_rope_dim,)),
            ], -1)
            sc = jnp.einsum("bqhd,bkhd->bqhk", qx, k_blk) * scale
            kv_pos = blk * kv_block + jnp.arange(kv_block)
            mask = (
                (kv_pos[None, :] <= q_pos[:, None])[None, :, None, :]
                & (kv_pos[None, :] < qlens[:, None])[:, None, None, :]
            )
            sc = jnp.where(mask, sc, neg)
            m_new = jnp.maximum(mm, jnp.max(sc, -1))
            m_safe = jnp.where(m_new <= neg, 0.0, m_new)
            pp = jnp.where(mask, jnp.exp(sc - m_safe[..., None]), 0.0)
            alpha = jnp.where(mm <= neg, 0.0, jnp.exp(mm - m_safe))
            ll = ll * alpha + jnp.sum(pp, -1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bqhk,bkhv->bqhv", pp, v_blk)
            return (acc, m_new, ll), None

        (acc, _, ll), _ = jax.lax.scan(
            step, (acc0, m0, l0), (lat_b, rope_b, jnp.arange(nkv)),
            unroll=True if _fref.UNROLL_SCANS else 1,
        )
        ll = jnp.where(ll == 0.0, 1.0, ll)
        return acc / ll[..., None]

    out = jax.lax.map(one_chunk, (qc, jnp.arange(ncq)))
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, h, m.v_head_dim)
    return out.astype(q_nope.dtype)


def _mla_attention(cfg: ModelConfig, p, x, positions, *, mode, cache, layer,
                   meta, backend):
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.num_q_heads
    scale = (m.qk_nope_dim + m.qk_rope_dim) ** -0.5
    q_nope, q_rope = _mla_q(cfg, p, x, positions)
    latent, k_rope = _mla_latent(cfg, p, x, positions)
    new_cache = None

    if mode in ("prefill", "decode"):
        # MLA caches the compressed [latent | k_rope] row (one 'KV head');
        # its paged reads run on the xla path, so its writes do too
        kv_row = jnp.concatenate([latent, k_rope], axis=-1)[:, :, None, :]
        new_cache = {"kv_pages": write_pages(cache["kv_pages"], layer,
                                             kv_row, meta["slots"])}

    if mode == "prefill" and cfg.mla_fused_prefill:
        qlens = meta["query_lens"]
        o = _mla_prefill_fused(cfg, p, q_nope, q_rope, latent, k_rope,
                               qlens, scale=scale)
        out = L.linear(p["wo"], o.reshape(b, s, -1).astype(x.dtype))
        return constrain(out, "batch", "seq_sp", "embed"), new_cache

    if mode in ("train", "prefill"):
        # expanded form: per-head keys/values from the latent
        k_nope = L.linear(p["wk_b"], latent).reshape(b, s, h, m.qk_nope_dim)
        v = L.linear(p["wv_b"], latent).reshape(b, s, h, m.v_head_dim)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                      (b, s, h, m.qk_rope_dim))], axis=-1
        )
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        q = constrain(q, "batch", "seq", "heads", "head_dim")
        k = constrain(k, "batch", "seq", "heads", "head_dim")
        v = constrain(v, "batch", "seq", "heads", "head_dim")
        kv_block = min(1024, s)
        while s % kv_block:
            kv_block //= 2
        if mode == "prefill":
            o = flash_attention_xla(q, k, v, causal=True, scale=scale,
                                    kv_block=kv_block,
                                    kv_len=meta["query_lens"])
        else:
            o = flash_attention_xla(q, k, v, causal=True, scale=scale,
                                    kv_block=kv_block)
    elif mode == "decode":
        # absorbed form: queries move into the latent space; the paged cache
        # is MQA over the 576-dim compressed rows
        pt, ctx = meta["page_table"], meta["context_lens"]
        wkb = p["wk_b"]["w"].reshape(m.kv_lora_rank, h, m.qk_nope_dim)
        q_abs = jnp.einsum("bshn,rhn->bshr", q_nope.astype(jnp.float32),
                           wkb.astype(jnp.float32))
        q_eff = jnp.concatenate(
            [q_abs.astype(x.dtype), q_rope], axis=-1
        )  # [B,1,H, r+rope]
        o_lat = attn_backend.decode_attention(
            "xla", q_eff[:, 0], new_cache["kv_pages"], layer, pt, ctx,
            scale=scale, v_dim=m.kv_lora_rank,
            blockscan=cfg.decode_blockscan,
        )  # [B, H, r]
        wvb = p["wv_b"]["w"].reshape(m.kv_lora_rank, h, m.v_head_dim)
        o = jnp.einsum("bhr,rhv->bhv", o_lat.astype(jnp.float32),
                       wvb.astype(jnp.float32))[:, None].astype(x.dtype)
        del backend
    else:
        raise ValueError(mode)

    out = L.linear(p["wo"], o.reshape(b, s, -1).astype(x.dtype))
    return constrain(out, "batch", "seq_sp", "embed"), new_cache


def kv_cache_dims(cfg: ModelConfig) -> tuple[int, int, int]:
    """(num_kv_heads, k_dim, v_dim) of the paged cache rows; v_dim 0 means
    V is a view into K (MLA latent)."""
    if cfg.mla.kv_lora_rank:
        return 1, cfg.mla.kv_lora_rank + cfg.mla.qk_rope_dim, 0
    dh = cfg.resolved_head_dim
    return cfg.num_kv_heads, dh, dh
