"""Plain reference for a dense GQA decoder: the Llama / Qwen2 equations.

    h   = x + o_proj(attn(rope(q), rope(k), v))    q/k/v = proj(rmsnorm(x))
    out = h + down(silu(gate(rmsnorm(h))) * up(rmsnorm(h)))
    logits = rmsnorm(x_L) @ embed.T                  (tied embeddings)

RMSNorm is `x * rsqrt(mean(x**2) + eps) * w`; RoPE rotates the halves
(x[:D/2], x[D/2:]) by position * theta**(-2i/D); attention is causal with
scale D**-0.5, each KV head shared by Hq/Hkv query heads; q/k/v carry a
bias where the configuration says `qkv_bias`.  Everything is float32 with
matmuls at HIGHEST precision, one full sequence at a time, no cache, no
batching and no kernels.  It imports nothing of the system under test: its
weights come from `bench.weights` with the run's seed, layer by layer.

The control, the precision below the configuration's bfloat16, put in the
program's place (`bench/control.py` reads it): "fp8", where every linear
layer, the LM head included, multiplies operands rounded to float8 e4m3
(activations scaled per row, weights per output column), and the cached K
and V are rounded to e4m3 per token and head: an fp8 KV cache.  Norms,
RoPE, softmax and the rest of attention stay in float32.

The family's weight layout (`bench.weights`) and its map onto the
program's parameter tree live here too, so that another family is another
module beside this one.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
PAD = 512  # sequences are padded to a multiple of this (fewer programs)
Q_BLOCK = 512  # query rows per attention block (bounds the score matrix)


# ---------------------------------------------------------------------------
# sizes, weight layout, the program's parameter tree
# ---------------------------------------------------------------------------


def dims(spec: dict) -> dict:
    """The sizes the weights, the reference and the work counts use, from
    a configuration file's published keys."""
    d = spec["hidden_size"]
    hq = spec["num_attention_heads"]
    return {
        "d": d, "hq": hq, "hkv": spec["num_key_value_heads"],
        "dh": spec.get("head_dim") or d // hq,
        "ff": spec["intermediate_size"], "vocab": spec["vocab_size"],
        "layers": spec["num_hidden_layers"],
        "qkv_bias": bool(spec.get("qkv_bias", False)),
    }


def layer_layout(spec: dict) -> dict[str, tuple[tuple[int, ...], int, float]]:
    """name -> (shape, exponent, offset) of one layer's tensors, laid out
    `x @ w` (input dimension first)."""
    m = dims(spec)
    d, q, kv, ff = m["d"], m["hq"] * m["dh"], m["hkv"] * m["dh"], m["ff"]
    e = W.scale_exponent
    out = {
        "attn_norm": ((d,), -2, 1.0),
        "q": ((d, q), e(d), 0.0),
        "k": ((d, kv), e(d), 0.0),
        "v": ((d, kv), e(d), 0.0),
        "o": ((q, d), e(q), 0.0),
        "mlp_norm": ((d,), -2, 1.0),
        "gate": ((d, ff), e(d), 0.0),
        "up": ((d, ff), e(d), 0.0),
        "down": ((ff, d), e(ff), 0.0),
    }
    if m["qkv_bias"]:
        out.update(q_bias=((q,), -3, 0.0), k_bias=((kv,), -3, 0.0),
                   v_bias=((kv,), -3, 0.0))
    return out


def global_layout(spec: dict) -> dict[str, tuple[tuple[int, ...], int, float]]:
    """The embedding (tied to the LM head) and the final norm."""
    m = dims(spec)
    return {"embed": ((m["vocab"], m["d"]), W.scale_exponent(m["d"]), 0.0),
            "final_norm": ((m["d"],), -2, 1.0)}


def program_mismatch(cfg) -> list[str]:
    """What keeps the program's config from being this family."""
    if cfg.family != "dense" or not cfg.tie_embeddings:
        return [f"family {cfg.family}, tied {cfg.tie_embeddings}: this "
                f"reference is a dense decoder with tied embeddings"]
    return []


def program_params(cfg, w: dict) -> dict:
    """The seeded weights (`bench.weights.stacked_weights`) in the
    program's parameter tree; call under jit."""
    lw, gw = w["layers"], w["global"]

    def lin(name):
        p = {"w": lw[name]}
        if cfg.qkv_bias and f"{name}_bias" in lw:
            p["b"] = lw[f"{name}_bias"]
        return p

    return {
        "embed": {"table": gw["embed"]},
        "ln_f": {"scale": gw["final_norm"]},
        "blocks": {
            "ln1": {"scale": lw["attn_norm"]},
            "attn": {"wq": lin("q"), "wk": lin("k"), "wv": lin("v"),
                     "wo": {"w": lw["o"]}},
            "ln2": {"scale": lw["mlp_norm"]},
            "mlp": {"gate": {"w": lw["gate"]}, "up": {"w": lw["up"]},
                    "down": {"w": lw["down"]}},
        },
    }


# ---------------------------------------------------------------------------
# the reference forward pass
# ---------------------------------------------------------------------------


def _mm_exact(x, w):
    return jnp.dot(x, w, precision=HIGHEST, preferred_element_type=F32)


def _fp8(x, axis):
    """Round to float8 e4m3 with the largest magnitude along `axis` mapped
    to e4m3's largest finite value (448), and back to float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                    1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm_fp8(x, w):
    return _mm_exact(_fp8(x, -1), _fp8(w, 0))


MATMULS = {"exact": _mm_exact, "fp8": _mm_fp8}


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos[:, None].astype(F32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, scale):
    """Causal GQA attention.  q [N, Hq, D], k/v [N, Hkv, D] -> [N, Hq, D]."""
    n, hq, dh = q.shape
    hkv = k.shape[1]
    qg = q.reshape(n // Q_BLOCK, Q_BLOCK, hkv, hq // hkv, dh)
    kpos = jnp.arange(n)

    def block(args):
        qb, i = args
        s = jnp.einsum("qhgd,khd->hgqk", qb, k, precision=HIGHEST) * scale
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, (qg, jnp.arange(n // Q_BLOCK)))
    return out.reshape(n, hq, dh)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _block(shape_key, matmul, w, x):
    """One decoder layer over one padded sequence x [N, d]."""
    dh, hq, hkv, theta, eps = shape_key
    mm = MATMULS[matmul]
    n = x.shape[0]
    pos = jnp.arange(n)
    h = _rms(x, w["attn_norm"], eps)
    q, k, v = mm(h, w["q"]), mm(h, w["k"]), mm(h, w["v"])
    if "q_bias" in w:
        q, k, v = q + w["q_bias"], k + w["k_bias"], v + w["v_bias"]
    q = _rope(q.reshape(n, hq, dh), pos, theta)
    k = _rope(k.reshape(n, hkv, dh), pos, theta)
    v = v.reshape(n, hkv, dh)
    if matmul == "fp8":  # the control's fp8 KV cache
        k, v = _fp8(k, -1), _fp8(v, -1)
    o = _attention(q, k, v, dh ** -0.5).reshape(n, hq * dh)
    x = x + mm(o, w["o"])
    h = _rms(x, w["mlp_norm"], eps)
    return x + mm(jax.nn.silu(mm(h, w["gate"])) * mm(h, w["up"]), w["down"])


def _shape_key(spec):
    m = dims(spec)
    return (m["dh"], m["hq"], m["hkv"], float(spec["rope_theta"]),
            float(spec["rms_norm_eps"]))


def final_hidden(spec: dict, seed: int, seqs: list[np.ndarray],
                 matmuls: tuple[str, ...] = ("exact",)):
    """Last-layer hidden states [N, d] of every sequence, padded to a
    multiple of PAD rows, one list per entry of `matmuls`, all run layer by
    layer over the same regenerated weights; and the global weights."""
    base = W.seed_key(seed)
    key = _shape_key(spec)
    glob = jax.jit(functools.partial(W.global_weights, spec, dtype=F32))(base)
    layer_w = jax.jit(functools.partial(W.layer_weights, spec, dtype=F32))
    padded = [jnp.asarray(np.pad(t, (0, -len(t) % PAD))) for t in seqs]
    streams = {mmn: [glob["embed"][p] for p in padded] for mmn in matmuls}
    for layer in range(spec["num_hidden_layers"]):
        w = layer_w(base, jnp.int32(layer))
        for mmn, hs in streams.items():
            streams[mmn] = [_block(key, mmn, w, h) for h in hs]
        del w
    return [streams[mmn] for mmn in matmuls], glob


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def head_readings(eps, control, draw, glob, h, h_ctl, nxt, temperature,
                  key):
    """Readings of the exact LM head at every position p of one padded
    sequence, for the token `nxt[p]` the system put there:

      gap         how far its logit lies below the exact best
      mass_above  the probability, at `temperature`, of the tokens whose
                  logit lies above its own: it is inside the nucleus of
                  top-p sampling exactly when this is below top_p

    With a `control` name, "control_gap": the gap of the control's own
    first choice.  With `draw`, "drawn_mass_above": mass_above of a token
    drawn from the whole distribution at `temperature` with `key` (the
    sampler with its top-p filter left out).  The head runs PAD rows at a
    time."""
    embed = glob["embed"]
    norm = lambda x: _rms(x, glob["final_norm"], eps)  # noqa: E731
    rows = lambda x: x.reshape((-1, PAD) + x.shape[1:])  # noqa: E731
    t = jnp.where(temperature > 0, temperature, 1.0)

    def above(logits, tok):
        own = jnp.take_along_axis(logits, tok[:, None], 1)
        p = jax.nn.softmax(logits / t, axis=-1)
        return jnp.sum(jnp.where(logits > own, p, 0.0), axis=-1)

    def chunk(args):
        a, b, tok, i = args
        logits = _mm_exact(norm(a), embed.T)
        best = jnp.max(logits, axis=-1)
        out = {"gap": best - jnp.take_along_axis(logits, tok[:, None],
                                                 1)[:, 0],
               "mass_above": above(logits, tok)}
        if control is not None:
            first = jnp.argmax(MATMULS[control](norm(b), embed.T), axis=-1)
            out["control_gap"] = best - jnp.take_along_axis(
                logits, first[:, None], 1)[:, 0]
        if draw:
            drawn = jax.random.categorical(jax.random.fold_in(key, i),
                                           logits / t, axis=-1)
            out["drawn_mass_above"] = above(logits, drawn)
        return out

    n = h.shape[0] // PAD
    out = jax.lax.map(chunk, (rows(h), rows(h_ctl), rows(nxt),
                              jnp.arange(n)))
    return {k: v.reshape(-1) for k, v in out.items()}
