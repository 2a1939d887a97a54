"""Model assembly: embed -> block stack (scan-over-layers) -> norm -> head.

Families:
  dense / moe / audio / vlm : uniform decoder blocks (attention + MLP/MoE)
  hybrid (zamba2)           : Mamba2 blocks with a weight-SHARED global
                              attention block every `shared_attn_period`
                              positions (6 invocations over 38 blocks)
  ssm (xlstm)               : groups of (slstm_period-1) mLSTM + 1 sLSTM

Layers are stacked and executed with jax.lax.scan so compile time is
independent of depth (essential for the 126-layer dry-run); train mode wraps
the block body in jax.checkpoint (full remat).

Entry points mirror the lowered programs:
  apply_train(cfg, params, batch)            -> (loss, metrics)
  apply_prefill(cfg, params, cache, batch)   -> (last_logits, new_cache)
  apply_decode(cfg, params, cache, batch)    -> (logits, new_cache)
  apply_unified(cfg, params, cache, batch)   -> (last_logits, new_cache)
                                             (token-packed decode+prefill;
                                             sample=True fuses last-token
                                             gather + sampling and returns
                                             (sampled_tokens, new_cache))
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.paged.kv_cache import make_kv_cache_specs
from repro.distributed.sharding import constrain
from repro.models import layers as L
from repro.models import sampling
from repro.models import ssm_blocks as S
from repro.models.attention import (
    attention, init_attention, kv_cache_dims, kv_write_meta,
)
from repro.models.moe import (
    init_moe, moe_ffn, moe_ffn_dropless, moe_ffn_dropless_ep,
)

# Roofline accounting mode: XLA's cost_analysis counts a while-loop body
# exactly once, so the depth-reduced roofline lowerings unroll the layer
# stack into straight-line HLO (repro.roofline flips this).
UNROLL_BLOCKS = False


def _scan(body, carry, xs):
    if not UNROLL_BLOCKS:
        return jax.lax.scan(body, carry, xs)
    length = jax.tree.leaves(xs)[0].shape[0] if xs is not None else 0
    ys = []
    for i in range(length):
        carry, y = body(carry, jax.tree.map(lambda t: t[i], xs))
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree.map(lambda *ts: jnp.stack(ts, 0), *ys)
    else:
        ys = None
    return carry, ys


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_decoder_block(cfg: ModelConfig, key, *, use_moe: bool):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "ln1": L.init_rms_norm(cfg.d_model, cfg.param_dtype),
        "attn": init_attention(cfg, k1),
        "ln2": L.init_rms_norm(cfg.d_model, cfg.param_dtype),
    }
    if use_moe:
        p["moe"] = init_moe(cfg, k2)
    else:
        p["mlp"] = L.init_mlp(k3, cfg.d_model, cfg.d_ff,
                              dtype=cfg.param_dtype, fused=cfg.fused_mlp)
    del k4
    return p


def _moe_layout(cfg: ModelConfig) -> tuple[int, int]:
    """(num_leading_dense_blocks, num_scanned_blocks)."""
    lead = cfg.moe.first_k_dense if cfg.moe.num_experts else 0
    return lead, cfg.num_layers - lead


def init(cfg: ModelConfig, key) -> dict:
    keys = jax.random.split(key, 8)
    p: dict[str, Any] = {
        "embed": L.init_embedding(keys[0], cfg.vocab_size, cfg.d_model,
                                  cfg.param_dtype),
        "ln_f": L.init_rms_norm(cfg.d_model, cfg.param_dtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = L.init_linear(keys[1], cfg.d_model, cfg.vocab_size,
                                     dtype=cfg.param_dtype)

    if cfg.family in ("dense", "moe", "audio", "vlm"):
        lead, n_scan = _moe_layout(cfg)
        if lead:
            p["lead_blocks"] = [
                _init_decoder_block(cfg, k, use_moe=False)
                for k in jax.random.split(keys[2], lead)
            ]
        use_moe = cfg.moe.num_experts > 0
        bkeys = jax.random.split(keys[3], n_scan)
        p["blocks"] = jax.vmap(
            lambda k: _init_decoder_block(cfg, k, use_moe=use_moe)
        )(bkeys)
    elif cfg.family == "hybrid":
        n_mamba, n_attn, _ = hybrid_layout(cfg)
        p["mamba"] = jax.vmap(lambda k: S.init_mamba2_block(cfg, k))(
            jax.random.split(keys[4], n_mamba)
        )
        p["mamba_ln"] = jax.vmap(
            lambda k: L.init_rms_norm(cfg.d_model, cfg.param_dtype)
        )(jax.random.split(keys[5], n_mamba))
        p["shared"] = _init_decoder_block(cfg, keys[6], use_moe=False)
    elif cfg.family == "ssm":
        n_m, n_s, _ = xlstm_layout(cfg)
        p["mlstm"] = jax.vmap(lambda k: S.init_mlstm_block(cfg, k))(
            jax.random.split(keys[4], n_m)
        )
        p["mlstm_ln"] = jax.vmap(
            lambda k: L.init_rms_norm(cfg.d_model, cfg.param_dtype)
        )(jax.random.split(keys[5], n_m))
        p["slstm"] = jax.vmap(lambda k: S.init_slstm_block(cfg, k))(
            jax.random.split(keys[6], n_s)
        )
        p["slstm_ln"] = jax.vmap(
            lambda k: L.init_rms_norm(cfg.d_model, cfg.param_dtype)
        )(jax.random.split(keys[7], n_s))
    else:
        raise ValueError(cfg.family)
    return p


def hybrid_layout(cfg: ModelConfig):
    """zamba2: every `period`-th block is the shared attention block.
    Returns (n_mamba, n_attn, group_size) with layout
    [ (period-1) mamba + 1 shared-attn ] * n_attn + tail mamba."""
    period = cfg.ssm.shared_attn_period
    n_attn = cfg.num_layers // period
    n_mamba = cfg.num_layers - n_attn
    return n_mamba, n_attn, period - 1


def xlstm_layout(cfg: ModelConfig):
    period = cfg.ssm.slstm_period
    n_s = cfg.num_layers // period
    n_m = cfg.num_layers - n_s
    return n_m, n_s, period - 1


# ---------------------------------------------------------------------------
# cache construction
# ---------------------------------------------------------------------------


def attn_layer_count(cfg: ModelConfig) -> int:
    if cfg.family == "hybrid":
        return hybrid_layout(cfg)[1]
    if cfg.family == "ssm":
        return 0
    return cfg.num_layers


def make_cache_specs(cfg: ModelConfig, *, max_seqs: int, num_pages: int,
                     num_pools: int = 1):
    """ShapeDtypeStruct pytree of the serving cache. `num_pages` is PER
    POOL; num_pools = data-parallel degree (1 on a single host)."""
    specs: dict[str, Any] = {}
    n_attn = attn_layer_count(cfg)
    if n_attn:
        hkv, dk, dv = kv_cache_dims(cfg)
        specs["attn"] = make_kv_cache_specs(
            n_attn, hkv, num_pools, num_pages, cfg.page_size, dk, dv,
            cfg.param_dtype
        )
    if cfg.family == "hybrid":
        n_mamba = hybrid_layout(cfg)[0]
        per = S.mamba2_cache_specs(cfg, max_seqs)
        specs["mamba"] = {
            k: jax.ShapeDtypeStruct((n_mamba,) + v.shape, v.dtype)
            for k, v in per.items()
        }
    if cfg.family == "ssm":
        n_m, n_s, _ = xlstm_layout(cfg)
        per_m = S.mlstm_cache_specs(cfg, max_seqs)
        per_s = S.slstm_cache_specs(cfg, max_seqs)
        specs["mlstm"] = {
            k: jax.ShapeDtypeStruct((n_m,) + v.shape, v.dtype)
            for k, v in per_m.items()
        }
        specs["slstm"] = {
            k: jax.ShapeDtypeStruct((n_s,) + v.shape, v.dtype)
            for k, v in per_s.items()
        }
    return specs


def make_cache(cfg: ModelConfig, *, max_seqs: int, num_pages: int,
               num_pools: int = 1):
    cache = jax.tree.map(
        lambda sp: jnp.zeros(sp.shape, sp.dtype),
        make_cache_specs(cfg, max_seqs=max_seqs, num_pages=num_pages,
                         num_pools=num_pools),
    )
    # the mLSTM stabilizer starts at -inf
    if "mlstm" in cache:
        cache["mlstm"]["m"] = jnp.full_like(cache["mlstm"]["m"], -jnp.inf)
    if "slstm" in cache:
        cache["slstm"]["m"] = jnp.full_like(cache["slstm"]["m"], -jnp.inf)
    return cache


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _decoder_block(cfg, p, x, positions, *, mode, cache, layer, meta,
                   backend, kernel_cfg=None, shard=None):
    h, new_cache = attention(
        cfg, p["attn"], L.rms_norm(p["ln1"], x, cfg.norm_eps), positions,
        mode=mode, cache=cache, layer=layer, meta=meta, backend=backend,
        kernel_cfg=kernel_cfg, shard=shard,
    )
    x = x + h
    h2 = L.rms_norm(p["ln2"], x, cfg.norm_eps)
    if "moe" in p:
        # train: GShard capacity dispatch (GSPMD-sharded einsums);
        # serve: dropless sort + ragged grouped GEMM (inference never
        # drops); distributed serve: shard_map expert-parallel dropless
        # (§Perf: the GSPMD-lowered global sort/ragged_dot replicates)
        from repro.distributed import sharding as dsh
        if mode == "train":
            y, aux = moe_ffn(cfg, p["moe"], h2)
        elif cfg.moe_ep_serve and dsh.active():
            y, aux = moe_ffn_dropless_ep(cfg, p["moe"], h2)
        else:
            y, aux = moe_ffn_dropless(cfg, p["moe"], h2)
    else:
        y, aux = L.mlp(p["mlp"], h2), jnp.zeros((), jnp.float32)
    return x + y, new_cache, aux


def _embed_inputs(cfg, params, inputs):
    if cfg.input_kind == "embeds" and inputs.ndim == 3:
        x = inputs.astype(cfg.param_dtype)
    else:
        x = L.embed(params["embed"], inputs)
    return constrain(x, "batch", "seq_sp", "embed")


def _head(cfg, params, x):
    x = L.rms_norm(params["ln_f"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = L.unembed(params["embed"], x)
    else:
        logits = L.linear(params["lm_head"], x).astype(jnp.float32)
    return constrain(logits, "batch", "seq", "vocab")


def forward(cfg: ModelConfig, params, inputs, positions, *, mode: str,
            cache=None, meta=None, backend: str = "xla", kernel_cfg=None,
            shard=None, head: bool = True):
    """Returns (logits [B,S,V] fp32, new_cache, aux_loss); with
    `head=False` the final hidden states [B,S,d] stand in for the logits,
    for a caller that projects only the rows it needs.  `kernel_cfg`
    (a heuristics.KernelConfig or None) is STATIC dispatch metadata —
    chosen host-side per launch, baked into the traced program.  `shard`
    (a sharding.ShardCtx or None) marks a per-device invocation inside
    the serving mesh executor's shard_map; only the attention head axis
    is sharded, everything else runs replicated.

    The attention cache is one stacked pool; it is carried through the
    layer loop (never passed as per-layer xs/ys), and each layer writes
    its rows into it at its own index, so a donated pool is updated in
    place."""
    x = _embed_inputs(cfg, params, inputs)
    meta = meta or {}
    aux_total = jnp.zeros((), jnp.float32)
    new_cache: dict[str, Any] = {}
    remat = mode == "train"
    attn = (cache or {}).get("attn")
    if attn is not None:
        meta = kv_write_meta(cfg, meta, positions, mode=mode,
                             pool=attn["kv_pages"], backend=backend)

    if cfg.family in ("dense", "moe", "audio", "vlm"):
        lead, _ = _moe_layout(cfg)
        for i, lp in enumerate(params.get("lead_blocks", [])):
            x, attn, aux = _decoder_block(cfg, lp, x, positions, mode=mode,
                                          cache=attn, layer=i, meta=meta,
                                          backend=backend,
                                          kernel_cfg=kernel_cfg, shard=shard)
            aux_total += aux

        def body(carry, per_layer):
            x, aux, attn = carry
            p_l, layer = per_layer
            x, attn, a = _decoder_block(cfg, p_l, x, positions, mode=mode,
                                        cache=attn, layer=layer, meta=meta,
                                        backend=backend,
                                        kernel_cfg=kernel_cfg, shard=shard)
            return (x, aux + a, attn), None

        if remat:
            body = jax.checkpoint(body)
        n_scan = jax.tree.leaves(params["blocks"])[0].shape[0]
        layers = lead + jnp.arange(n_scan, dtype=jnp.int32)
        (x, aux_total, attn), _ = _scan(
            body, (x, aux_total, attn), (params["blocks"], layers)
        )
        if attn is not None:
            new_cache["attn"] = attn

    elif cfg.family == "hybrid":
        x, new_cache, aux_total = _hybrid_forward(
            cfg, params, x, positions, mode=mode, cache=cache, meta=meta,
            backend=backend, remat=remat, kernel_cfg=kernel_cfg,
        )
    elif cfg.family == "ssm":
        x, new_cache, aux_total = _xlstm_forward(
            cfg, params, x, positions, mode=mode, cache=cache, meta=meta,
            remat=remat,
        )
    else:
        raise ValueError(cfg.family)

    out = _head(cfg, params, x) if head else x
    return out, (new_cache or None), aux_total


def _serve_masks(mode, meta, b, s):
    if mode == "prefill":
        qlens = meta["query_lens"]
        valid = jnp.arange(s)[None, :] < qlens[:, None]
        return valid, qlens
    if mode == "decode":
        live = meta["context_lens"] > 0
        return live[:, None], live.astype(jnp.int32)
    return None, None


def _hybrid_forward(cfg, params, x, positions, *, mode, cache, meta, backend,
                    remat, kernel_cfg=None):
    n_mamba, n_attn, group = hybrid_layout(cfg)
    b, s, _ = x.shape
    valid, seq_lens = _serve_masks(mode, meta, b, s)
    m_cache = (cache or {}).get("mamba")
    a_cache = (cache or {}).get("attn")
    aux = jnp.zeros((), jnp.float32)

    def mamba_body(x, per_layer):
        p_l, ln_l, c_l = per_layer
        h, nc = S.mamba2_block(
            cfg, p_l, L.rms_norm(ln_l, x, cfg.norm_eps), mode=mode,
            cache=c_l, valid=valid, seq_lens=seq_lens,
        )
        return x + h, nc

    if remat:
        mamba_body = jax.checkpoint(mamba_body)

    def mamba_slice(tree, lo, hi):
        return jax.tree.map(lambda t: t[lo:hi], tree)

    new_m = []
    off = 0
    for g in range(n_attn):
        xs = (
            mamba_slice(params["mamba"], off, off + group),
            mamba_slice(params["mamba_ln"], off, off + group),
            mamba_slice(m_cache, off, off + group) if m_cache is not None else None,
        )
        x, nc = _scan(mamba_body, x, xs)
        new_m.append(nc)
        off += group
        x, a_cache, a = _decoder_block(cfg, params["shared"], x, positions,
                                       mode=mode, cache=a_cache, layer=g,
                                       meta=meta, backend=backend,
                                       kernel_cfg=kernel_cfg)
        aux += a
    if off < n_mamba:  # tail
        xs = (
            mamba_slice(params["mamba"], off, n_mamba),
            mamba_slice(params["mamba_ln"], off, n_mamba),
            mamba_slice(m_cache, off, n_mamba) if m_cache is not None else None,
        )
        x, nc = _scan(mamba_body, x, xs)
        new_m.append(nc)

    new_cache = {}
    if m_cache is not None:
        new_cache["mamba"] = jax.tree.map(
            lambda *ts: jnp.concatenate(ts, 0), *new_m
        )
        new_cache["attn"] = a_cache
    return x, new_cache, aux


def _xlstm_forward(cfg, params, x, positions, *, mode, cache, meta, remat):
    n_m, n_s, group = xlstm_layout(cfg)
    b, s, _ = x.shape
    valid, seq_lens = _serve_masks(mode, meta, b, s)
    m_cache = (cache or {}).get("mlstm")
    s_cache = (cache or {}).get("slstm")
    del positions  # xLSTM is position-free (recurrence carries order)

    def mlstm_body(x, per_layer):
        p_l, ln_l, c_l = per_layer
        h, nc = S.mlstm_block(
            cfg, p_l, L.rms_norm(ln_l, x, cfg.norm_eps), mode=mode,
            cache=c_l, valid=valid, seq_lens=seq_lens,
        )
        return x + h, nc

    if remat:
        mlstm_body = jax.checkpoint(mlstm_body)

    def tslice(tree, lo, hi):
        return jax.tree.map(lambda t: t[lo:hi], tree)

    new_m, new_s = [], []
    off = 0
    for g in range(n_s):
        xs = (
            tslice(params["mlstm"], off, off + group),
            tslice(params["mlstm_ln"], off, off + group),
            tslice(m_cache, off, off + group) if m_cache is not None else None,
        )
        x, nc = _scan(mlstm_body, x, xs)
        new_m.append(nc)
        off += group
        c_l = (jax.tree.map(lambda t: t[g], s_cache)
               if s_cache is not None else None)
        ln = jax.tree.map(lambda t: t[g], params["slstm_ln"])
        p_l = jax.tree.map(lambda t: t[g], params["slstm"])
        h, ncs = S.slstm_block(cfg, p_l, L.rms_norm(ln, x, cfg.norm_eps),
                               mode=mode, cache=c_l, valid=valid)
        x = x + h
        new_s.append(ncs)
    if off < n_m:
        xs = (
            tslice(params["mlstm"], off, n_m),
            tslice(params["mlstm_ln"], off, n_m),
            tslice(m_cache, off, n_m) if m_cache is not None else None,
        )
        x, nc = _scan(mlstm_body, x, xs)
        new_m.append(nc)

    new_cache = {}
    if m_cache is not None:
        new_cache["mlstm"] = jax.tree.map(
            lambda *ts: jnp.concatenate(ts, 0), *new_m
        )
        new_cache["slstm"] = jax.tree.map(lambda *ts: jnp.stack(ts, 0), *new_s)
    return x, new_cache, jnp.zeros((), jnp.float32)


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------


def default_positions(cfg: ModelConfig, b: int, s: int):
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    if cfg.rope_style == "mrope":
        pos = jnp.broadcast_to(pos[None], (3, b, s))
    return pos


def apply_train(cfg: ModelConfig, params, batch, *, backend="xla"):
    """batch: {'inputs': [B,S] or [B,S,d], 'labels': [B,S], 'positions'?}.
    Returns (loss, metrics)."""
    inputs, labels = batch["inputs"], batch["labels"]
    b, s = labels.shape
    positions = batch.get("positions")
    if positions is None:
        positions = default_positions(cfg, b, s)
    logits, _, aux = forward(cfg, params, inputs, positions, mode="train",
                             backend=backend)
    logp = jax.nn.log_softmax(logits, axis=-1)
    mask = (labels >= 0).astype(jnp.float32)
    ll = jnp.take_along_axis(
        logp, jnp.clip(labels, 0)[..., None], axis=-1
    )[..., 0]
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    ce = -jnp.sum(ll * mask) / denom
    loss = ce + aux
    return loss, {"ce": ce, "aux": aux,
                  "tokens": jnp.sum(mask).astype(jnp.int32)}


def apply_prefill(cfg: ModelConfig, params, cache, batch, *, backend="xla",
                  kernel_cfg=None):
    """batch: inputs [B,S](ids) or [B,S,d], positions, page_table,
    context_lens, query_lens. Returns (last_token_logits [B,V], new_cache)."""
    meta = {k: batch[k] for k in ("page_table", "context_lens", "query_lens")}
    logits, new_cache, _ = forward(
        cfg, params, batch["inputs"], batch["positions"], mode="prefill",
        cache=cache, meta=meta, backend=backend, kernel_cfg=kernel_cfg,
    )
    # gather the logits at each sequence's last valid position
    last = jnp.clip(batch["query_lens"] - 1, 0)
    idx = last[:, None, None]
    out = jnp.take_along_axis(logits, idx, axis=1)[:, 0]
    return out, new_cache


def apply_prefill_cached(cfg: ModelConfig, params, cache, batch, *,
                         backend="xla", kernel_cfg=None):
    """Resumable prefill at context > 0: only this step's chunk of each
    prompt is embedded/computed (batch['inputs'] [B,S] holds chunk ids,
    positions are absolute, context_lens = prior context + chunk,
    query_lens = chunk).  The prior context — earlier prefill chunks, a
    prefix-cache hit, or both — is read back from the pages; attention
    writes the chunk's KV into the tail pages and attends over the full
    paged context.  Attention-family models only (SSM/hybrid recurrent
    state is not page-addressable).
    Returns (last_token_logits [B,V], new_cache)."""
    assert cfg.family in ("dense", "moe", "audio", "vlm") \
        and not cfg.mla.kv_lora_rank, \
        f"prefix caching unsupported for family={cfg.family!r}/MLA"
    meta = {k: batch[k] for k in ("page_table", "context_lens", "query_lens")}
    logits, new_cache, _ = forward(
        cfg, params, batch["inputs"], batch["positions"],
        mode="prefill_cached", cache=cache, meta=meta, backend=backend,
        kernel_cfg=kernel_cfg,
    )
    last = jnp.clip(batch["query_lens"] - 1, 0)
    out = jnp.take_along_axis(logits, last[:, None, None], axis=1)[:, 0]
    return out, new_cache


def apply_unified(cfg: ModelConfig, params, cache, batch, *, backend="xla",
                  kernel_cfg=None, num_decode_seqs: int = 0,
                  sample: bool = False, seed: int = 0,
                  return_logits: bool = False, shard=None,
                  max_draft: int = 0):
    """Token-packed unified step: ONE executable for decode rows, fresh
    prefill chunks, and resumed/cached chunks — and, with `sample=True`,
    for the last-token gather + sampling too, so the only thing that
    crosses back to the host per step is [S] sampled token ids.

    batch: inputs [1, T] packed token ids, positions [1, T] absolute
    per-token positions (packed-position RoPE: each token rotates by its
    own sequence position, not its row index), page_table [S, Np],
    context_lens [S], query_lens [S], query_start_loc [S+1], and
    slot_mapping [1, T] pool-local KV write slots (trash slot for padded
    tokens).  Sequences [0, num_decode_seqs) are the static decode region
    (one row per batch slot, dead slots context_lens == 0);
    `num_decode_seqs` is static dispatch metadata like `kernel_cfg`.

    Fused sampling (`sample=True`) adds per-sequence sampling params to
    the batch — temperature / top_p [S] f32, top_k / stream_ids /
    num_generated [S] i32 — and derives each row's PRNG key in-graph from
    (seed, stream id, tokens generated), see models.sampling.  When
    `prev_tokens` [S] and `token_source` [1, T] are present, input rows
    with `token_source >= 0` take their id from `prev_tokens[source]`
    instead of `inputs` — the async double-buffered engine packs the next
    step before the previous step's tokens reach the host, leaving the
    just-sampled ids on device.

    Speculative verification (`max_draft = K > 0`, requires `sample`):
    the batch carries `spec_lens` [S] i32 — rows with spec_lens == s > 0
    are decode requests packed as resumed chunks whose s+1 inputs are
    [last real token, draft_1..draft_s].  The target token for each verify
    position j (0 <= j <= s) is sampled from the logits at segment offset
    qlen-1-s+j with the PRNG counter num_generated + j — the EXACT key
    sequential decoding would fold for that draw — so accepted tokens are
    bit-identical to non-speculative decoding for every sampling config,
    not just greedy.  A row emits 1 + (longest prefix of drafts matching
    the sampled targets) tokens; the last emitted token is the bonus /
    correction sample.  Plain rows (spec_lens == 0, including completing
    prefill chunks) reduce to the ordinary fused sample in column 0.

    Returns (last_logits [S, V], new_cache) without sampling;
    (sampled_tokens [S], new_cache) with it; with `max_draft > 0`,
    (sampled_tokens [S, K+1], num_emitted [S], new_cache); and
    `return_logits=True` (the debug-logits flag — it reintroduces the
    [S, V] transfer, so it is off in production) inserts last_logits
    before new_cache in either shape.  Attention-family models only
    (SSM/hybrid state is slot-indexed, not page-addressable).

    `shard` (sharding.ShardCtx) marks a per-device invocation inside the
    mesh executor's shard_map: attention computes only the local head
    block and all-gathers outputs, so the epilogue here (last-token
    gather + sampling) runs replicated and bit-identically on every
    device."""
    assert cfg.family in ("dense", "moe", "audio", "vlm") \
        and not cfg.mla.kv_lora_rank, \
        f"unified packed step unsupported for family={cfg.family!r}/MLA"
    meta = {k: batch[k] for k in ("page_table", "context_lens",
                                  "query_lens", "query_start_loc",
                                  "slot_mapping")}
    meta["num_decode_seqs"] = num_decode_seqs
    inputs = batch["inputs"]
    if "token_source" in batch:
        src = batch["token_source"]
        inputs = jnp.where(src >= 0,
                           batch["prev_tokens"][jnp.clip(src, 0)], inputs)
    hidden, new_cache, _ = forward(
        cfg, params, inputs, batch["positions"], mode="unified",
        cache=cache, meta=meta, backend=backend, kernel_cfg=kernel_cfg,
        shard=shard, head=False,
    )
    t = hidden.shape[1]
    # per-sequence last-token rows of the packed stream; only these rows
    # (or the verify rows below) go through the LM head, never all T;
    # 0-length (padded) rows clamp to their segment start — the engine
    # never reads them
    last = batch["query_start_loc"][:-1] + jnp.clip(
        batch["query_lens"] - 1, 0)
    last = jnp.minimum(last, t - 1)
    last_logits = _head(cfg, params, hidden[:, last])[0]
    if not sample:
        return last_logits, new_cache
    if max_draft == 0:
        keys = sampling.request_keys(seed, batch["stream_ids"],
                                     batch["num_generated"])
        toks = sampling.sample_tokens(last_logits, batch["temperature"],
                                      batch["top_p"], batch["top_k"], keys)
        if return_logits:
            return toks, last_logits, new_cache
        return toks, new_cache
    # --- speculative verify: sample K+1 target tokens per row ----------
    # Verify position j of a row with s drafts reads the logits at
    # segment offset qlen-1-s+j: the logits that *predict* the token at
    # absolute position context_len-s+j.  Rows with s < K clamp their
    # leading columns to the segment start — those columns are never
    # consumed (num_emitted caps at s+1, plain rows use column 0 only).
    K = max_draft
    spec = batch["spec_lens"]                               # [S] i32
    S = spec.shape[0]
    offs = jnp.arange(K + 1, dtype=last.dtype)              # [K+1]
    start = batch["query_start_loc"][:-1]
    pos = last[:, None] - spec[:, None] + offs[None, :]     # [S, K+1]
    pos = jnp.clip(pos, start[:, None], last[:, None])
    pos = jnp.clip(pos, 0, t - 1)
    ver_logits = _head(cfg, params, hidden[0, pos])         # [S, K+1, V]
    # per-position keys at counters num_generated + j: the exact fold
    # sequence sequential decoding would use for these draws
    rep = lambda a: jnp.repeat(a, K + 1)
    streams = rep(batch["stream_ids"])
    ngen = (batch["num_generated"][:, None]
            + offs[None, :].astype(batch["num_generated"].dtype))
    keys = sampling.request_keys(seed, streams, ngen.reshape(-1))
    toks = sampling.sample_tokens(
        ver_logits.reshape(S * (K + 1), -1),
        rep(batch["temperature"]), rep(batch["top_p"]),
        rep(batch["top_k"]), keys).reshape(S, K + 1)
    # drafts are the packed *inputs* one slot ahead of each verify
    # position; accept the longest prefix where target == draft
    dpos = jnp.clip(pos[:, :-1] + 1, 0, inputs.shape[1] - 1)
    drafts = inputs[0, dpos]                                # [S, K]
    match = (toks[:, :-1] == drafts) & (offs[None, :-1] < spec[:, None])
    num_emitted = 1 + jnp.sum(
        jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
    num_emitted = num_emitted.astype(jnp.int32)
    if return_logits:
        return toks, num_emitted, last_logits, new_cache
    return toks, num_emitted, new_cache


def apply_decode(cfg: ModelConfig, params, cache, batch, *, backend="xla",
                 kernel_cfg=None):
    """batch: inputs [B,1] ids, positions [B,1], page_table, context_lens.
    Returns (logits [B,V], new_cache)."""
    meta = {k: batch[k] for k in ("page_table", "context_lens")}
    logits, new_cache, _ = forward(
        cfg, params, batch["inputs"], batch["positions"], mode="decode",
        cache=cache, meta=meta, backend=backend, kernel_cfg=kernel_cfg,
    )
    return logits[:, 0], new_cache


def init_abstract(cfg: ModelConfig):
    """Parameter ShapeDtypeStructs without allocation (dry-run path)."""
    return jax.eval_shape(
        functools.partial(init, cfg), jax.random.key(0)
    )
