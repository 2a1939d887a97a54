"""Mixture-of-Experts FFN (GShard/Switch-style capacity dispatch).

Dispatch is expressed as dense one-hot einsums over [tokens, experts,
capacity] so GSPMD can shard it: tokens on ('pod','data'), experts on
'model'. The all-to-alls emerge from the einsum reshardings — no manual
collectives, and the same code runs unsharded on CPU.

Supports top-k routing (k up to 8), shared experts (DeepSeek/Llama4 style),
capacity-factor token dropping, and the standard load-balancing auxiliary
loss. Dropless sort+ragged_dot is a documented alternative (DESIGN.md) —
capacity dispatch is what scales on the 16x16 mesh.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.models import layers as L


def init_moe(cfg: ModelConfig, key):
    m = cfg.moe
    dt = cfg.param_dtype
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    d, ff, e = cfg.d_model, m.d_ff_expert, m.num_experts
    std = d**-0.5
    p = {
        "router": L.init_linear(k1, d, e, dtype=jnp.float32),
        "w_gate": L.truncated_normal(k2, (e, d, ff), std, dt),
        "w_up": L.truncated_normal(k3, (e, d, ff), std, dt),
        "w_down": L.truncated_normal(k4, (e, ff, d), ff**-0.5, dt),
    }
    if m.num_shared_experts:
        p["shared"] = L.init_mlp(k5, d, ff * m.num_shared_experts, dtype=dt)
    return p


MOE_GROUP_SIZE = 4096  # GShard grouping: capacity is per-group, so the
# dispatch tensor is [G, Sg, E, C] with C = Sg*k*cf/E — independent of the
# global token count (G shards over the batch axes, E over 'model').


def moe_ffn(cfg: ModelConfig, p, x):
    """x [B, S, d] -> (y [B, S, d], aux_loss scalar). GShard capacity
    dispatch over token groups (train path)."""
    m = cfg.moe
    b, s, d = x.shape
    e, k = m.num_experts, m.top_k
    xt = x.reshape(b * s, d)
    t = xt.shape[0]
    sg = min(MOE_GROUP_SIZE, t)
    while t % sg:
        sg //= 2
    g = t // sg
    cap = max(int(sg * k * m.capacity_factor / e), 1)
    xg = xt.reshape(g, sg, d)
    xg = constrain(xg, "batch", None, "embed")

    logits = xg.astype(jnp.float32) @ p["router"]["w"]  # [G, Sg, E]
    probs = jax.nn.softmax(logits, axis=-1)

    # --- top-k routing with per-expert capacity positions (per group) -------
    topw, topi = jax.lax.top_k(probs, k)  # [G, Sg, k]
    topw = topw / jnp.clip(jnp.sum(topw, -1, keepdims=True), 1e-9)
    dispatch = jnp.zeros((g, sg, e, cap), jnp.bfloat16)
    combine = jnp.zeros((g, sg, e, cap), jnp.float32)
    counts = jnp.zeros((g, e), jnp.int32)
    for j in range(k):
        onehot = jax.nn.one_hot(topi[..., j], e, dtype=jnp.int32)  # [G,Sg,E]
        pos = counts[:, None] + jnp.cumsum(onehot, axis=1) - onehot
        within = (pos < cap) & (onehot > 0)
        pos_c = jnp.clip(pos, 0, cap - 1)
        sel = jax.nn.one_hot(pos_c, cap, dtype=jnp.float32) * within[..., None]
        dispatch = dispatch + sel.astype(jnp.bfloat16)
        combine = combine + sel * topw[..., j][..., None, None]
        counts = counts + jnp.sum(onehot * within, axis=1)

    dispatch = constrain(dispatch, "batch", None, "experts", "expert_cap")
    combine = constrain(combine, "batch", None, "experts", "expert_cap")

    # --- expert compute (the gsec->gecd resharding IS the all-to-all) -------
    xe = jnp.einsum("gsec,gsd->gecd", dispatch, xg.astype(jnp.bfloat16))
    xe = constrain(xe, "batch", "experts", "expert_cap", "embed")
    h = jax.nn.silu(
        jnp.einsum("gecd,edf->gecf", xe, p["w_gate"])
    ) * jnp.einsum("gecd,edf->gecf", xe, p["w_up"])
    # the expert dim already carries 'model' (EP); ff stays unsharded here
    h = constrain(h, "batch", "experts", "expert_cap", None)
    ye = jnp.einsum("gecf,efd->gecd", h, p["w_down"])
    ye = constrain(ye, "batch", "experts", "expert_cap", "embed")
    y = jnp.einsum("gsec,gecd->gsd", combine.astype(ye.dtype), ye)
    y = y.reshape(t, d)

    # --- shared experts + aux loss -------------------------------------------
    if "shared" in p:
        y = y + L.mlp(p["shared"], xt).astype(y.dtype)

    # Switch-style load-balance loss: E * sum_e f_e * p_e
    frac_tokens = jnp.mean(
        jax.nn.one_hot(topi[..., 0], e, dtype=jnp.float32), axis=(0, 1)
    )
    frac_probs = jnp.mean(probs, axis=(0, 1))
    aux = e * jnp.sum(frac_tokens * frac_probs) * m.router_aux_coef
    return y.reshape(b, s, d).astype(x.dtype), aux


def moe_ffn_dropless_ep(cfg: ModelConfig, p, x):
    """Expert-parallel dropless MoE for DISTRIBUTED serving (§Perf).

    The plain dropless path (sort + global ragged_dot) cannot be
    partitioned by GSPMD: it replicates the [T·k, d] token workspace per
    device and all-reduces TB-scale outputs (deepseek-v2 prefill_32k
    baseline: 12.6 TB/device all-reduce, 244 GiB temp). Here each 'model'
    shard keeps its E/16 experts, processes the tokens routed to them
    (activations are already replicated across 'model' between blocks),
    and one psum over 'model' combines expert outputs — no token exchange
    at all.
    """
    from jax.sharding import PartitionSpec as P

    from repro.distributed import sharding as dsh

    mesh = dsh._mesh()
    rules = dsh._rules()
    m = cfg.moe
    e = m.num_experts
    model_n = mesh.shape["model"]
    if e % model_n:
        return moe_ffn_dropless(cfg, p, x)
    batch_axes = rules.get("batch") or ()
    data_n = 1
    for a in batch_axes:
        data_n *= mesh.shape[a]
    if x.shape[0] % data_n:
        batch_axes, data_n = (), 1  # tiny batch: replicate over data
    e_loc = e // model_n
    b, s, d = x.shape
    k = m.top_k

    def body(xl, router_w, wg, wu, wd):
        # FULLY manual: xl [B_loc,S,d] is this shard's tokens; wg/wu/wd its
        # E/16 experts. Tokens never move; one psum combines experts.
        midx = jax.lax.axis_index("model")
        xt = xl.reshape(-1, d)
        t = xt.shape[0]
        logits = xt.astype(jnp.float32) @ router_w
        probs = jax.nn.softmax(logits, axis=-1)
        topw, topi = jax.lax.top_k(probs, k)
        topw = topw / jnp.clip(jnp.sum(topw, -1, keepdims=True), 1e-9)
        e_flat = topi.reshape(-1) - midx * e_loc  # local expert id
        local = (e_flat >= 0) & (e_flat < e_loc)
        e_sort_key = jnp.where(local, e_flat, e_loc)  # non-local -> tail
        order = jnp.argsort(e_sort_key)
        tok_of = order // k
        xs = xt[tok_of].astype(wg.dtype)
        group_sizes = jnp.bincount(e_sort_key, length=e_loc + 1
                                   ).astype(jnp.int32)[:e_loc]
        h = jax.nn.silu(jax.lax.ragged_dot(xs, wg, group_sizes)) * \
            jax.lax.ragged_dot(xs, wu, group_sizes)
        ys = jax.lax.ragged_dot(h, wd, group_sizes)
        w_flat = topw.reshape(-1)[order].astype(jnp.float32)
        w_flat = w_flat * local[order].astype(jnp.float32)
        y = jnp.zeros((t, d), jnp.float32).at[tok_of].add(
            ys.astype(jnp.float32) * w_flat[:, None])
        return jax.lax.psum(y, "model").reshape(xl.shape)

    y = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(batch_axes or None), P(), P("model"), P("model"),
                  P("model")),
        out_specs=P(batch_axes or None),
        check_vma=False,
    )(x, p["router"]["w"], p["w_gate"], p["w_up"], p["w_down"])
    if "shared" in p:
        y = y + L.mlp(p["shared"], x.reshape(-1, d)).astype(y.dtype
                                                            ).reshape(b, s, d)
    return y.astype(x.dtype), jnp.zeros((), jnp.float32)


def moe_ffn_dropless(cfg: ModelConfig, p, x):
    """Dropless MoE (serving path): sort tokens by expert + ragged grouped
    GEMM (jax.lax.ragged_dot). Inference never drops tokens — routing is
    exactly the dense-reference routing. Returns (y, aux=0)."""
    m = cfg.moe
    b, s, d = x.shape
    e, k = m.num_experts, m.top_k
    xt = x.reshape(b * s, d)
    t = xt.shape[0]

    logits = xt.astype(jnp.float32) @ p["router"]["w"]
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, k)  # [T, k]
    topw = topw / jnp.clip(jnp.sum(topw, -1, keepdims=True), 1e-9)

    cdt = p["w_gate"].dtype  # compute in the param dtype
    e_flat = topi.reshape(-1)  # [T*k]
    order = jnp.argsort(e_flat)  # stable
    tok_of = order // k  # source token per sorted row
    xs = xt[tok_of].astype(cdt)  # [T*k, d]
    group_sizes = jnp.bincount(e_flat, length=e).astype(jnp.int32)

    h = jax.nn.silu(
        jax.lax.ragged_dot(xs, p["w_gate"], group_sizes)
    ) * jax.lax.ragged_dot(xs, p["w_up"], group_sizes)
    ys = jax.lax.ragged_dot(h, p["w_down"], group_sizes)
    w_flat = topw.reshape(-1)[order].astype(jnp.float32)
    y = jnp.zeros((t, d), jnp.float32).at[tok_of].add(
        ys.astype(jnp.float32) * w_flat[:, None]
    )
    if "shared" in p:
        y = y + L.mlp(p["shared"], xt).astype(jnp.float32)
    return y.reshape(b, s, d).astype(x.dtype), jnp.zeros((), jnp.float32)
