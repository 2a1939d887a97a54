"""Operations and bytes the traffic needed, counted from its token events.

Nothing here reads a launched shape: padding, dead rows, pages read twice
and logits of rows that sample nothing earn no credit.  `m` is
`bench.weights.dims(spec)`; attention and weights are counted at the
configuration's own head size and dtype width `b` (bytes per element).

    prompt positions [start, end) of one request, computed in the window
    decode rows: the context length each decode row attended over
    sampled rows: tokens the LM head was needed for
"""
from __future__ import annotations


def matmul_params(m: dict) -> int:
    """Parameters of the per-layer matmuls (q, k, v, o, gate, up, down),
    over all layers; the embedding gather and the LM head are apart."""
    d, q, kv, ff = m["d"], m["hq"] * m["dh"], m["hkv"] * m["dh"], m["ff"]
    return m["layers"] * (d * q + 2 * d * kv + q * d + 3 * d * ff)


def attn_flops(m: dict, ctx: int) -> int:
    """QK^T and PV of one query row over `ctx` keys, all layers."""
    return 4 * m["layers"] * m["hq"] * m["dh"] * ctx


def prompt_attn_flops(m: dict, start: int, end: int) -> int:
    """Causal attention of prompt positions [start, end): position p
    attends over p + 1 keys."""
    keys = (end * (end + 1) - start * (start + 1)) // 2
    return 4 * m["layers"] * m["hq"] * m["dh"] * keys


def lm_head_flops(m: dict) -> int:
    return 2 * m["d"] * m["vocab"]


def model_flops(m: dict, prompts: list[tuple[int, int]],
                decode_ctx: list[int], sampled_rows: int) -> int:
    """Useful model FLOPs: 2 x matmul params per computed token, attention
    at each token's own context, the LM head once per sampled row."""
    tokens = sum(e - s for s, e in prompts) + len(decode_ctx)
    attn = sum(prompt_attn_flops(m, s, e) for s, e in prompts) \
        + sum(attn_flops(m, c) for c in decode_ctx)
    return 2 * matmul_params(m) * tokens + attn \
        + lm_head_flops(m) * sampled_rows


def decode_attn_work(m: dict, decode_ctx: list[int], b: int = 2) \
        -> tuple[int, int]:
    """(FLOPs, bytes) of the decode rows' attention: each row reads its
    query, its context's K and V once, and writes its output."""
    row = 2 * m["hq"] * m["dh"] * b
    kv = 2 * m["hkv"] * m["dh"] * b
    flops = sum(attn_flops(m, c) for c in decode_ctx)
    nbytes = m["layers"] * sum(row + kv * c for c in decode_ctx)
    return flops, nbytes


def chunk_attn_work(m: dict, prompts: list[tuple[int, int]], b: int = 2) \
        -> tuple[int, int]:
    """(FLOPs, bytes) of the prompt rows' attention, as if each request's
    positions computed in the window ran as one pass: queries and outputs
    once, the K and V of its context up to `end` read once."""
    row = 2 * m["hq"] * m["dh"] * b
    kv = 2 * m["hkv"] * m["dh"] * b
    flops = sum(prompt_attn_flops(m, s, e) for s, e in prompts)
    nbytes = m["layers"] * sum(row * (e - s) + kv * e for s, e in prompts)
    return flops, nbytes


def least_seconds(flops: int, nbytes: int, peaks: dict) -> float:
    """Roofline bound: the larger of compute time and memory time."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
