"""Ahead-of-time compiles for a described TPU v5e chip (no chip needed).

Each test lowers a paged-attention kernel, or one whole Qwen2.5-3B serving
step, at real widths for one chip of a `v5e:2x2` topology and compiles it
with the TPU compiler installed alongside JAX.  That compiler refuses what
the chip would refuse: block shapes Mosaic cannot tile, kernels over the
VMEM limit, programs over the device's memory.  Nothing runs, so these
say nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and test workers import
every test file.  Keep these compiles in this one file.
"""
from __future__ import annotations

import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.kernels
from repro.configs import ARCHS
from repro.core.attention.heuristics import KernelConfig
from repro.kernels.paged_attention import ops
from repro.models import model as M

HBM_BYTES = 16 * 10**9  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# (Hq, Hkv, D): Qwen2.5-3B, and SmolLM's head_dim 64, whose K|V pool row
# is 128 lanes
QWEN = (16, 2, 128)
SMOLLM = (9, 3, 64)
MAX_SEQS, PAGES, PAGE, PAGES_PER_SEQ, CHUNK_TOKENS = 8, 2048, 16, 128, 512
LAYERS = 36  # the kernels read the whole stacked pool at a layer index


def _kernel_args(sh, heads, dtype, *, seqs, tokens):
    hq, hkv, d = heads
    i32 = jnp.int32
    return dict(
        q=_spec(sh, (tokens, hq, d), dtype),
        kv=_spec(sh, (LAYERS, hkv, PAGES, PAGE, 2 * d), dtype),
        layer=_spec(sh, (), i32),
        pt=_spec(sh, (seqs, PAGES_PER_SEQ), i32),
        ctx=_spec(sh, (seqs,), i32),
        qsl=_spec(sh, (seqs + 1,), i32),
        qlens=_spec(sh, (seqs,), i32))


def _decode(variant, a):
    return jax.jit(lambda q, kv, layer, pt, ctx: ops.paged_attention_decode(
        q, kv, layer, pt, ctx, variant=variant, num_segments=8,
        interpret=False,
    )).lower(*(a[n] for n in ("q", "kv", "layer", "pt", "ctx")))


def _prefill(a):
    return jax.jit(lambda q, kv, layer, pt, ctx, qsl, ql:
                   ops.paged_attention_prefill(
                       q, kv, layer, pt, ctx, qsl, ql, block_q=16,
                       interpret=False,
                   )).lower(*(a[n] for n in ("q", "kv", "layer", "pt", "ctx",
                                             "qsl", "qlens")))


def _unified(variant, a):
    return jax.jit(lambda q, kv, layer, pt, ctx, qsl, ql:
                   ops.paged_attention_unified(
                       q, kv, layer, pt, ctx, qsl, ql,
                       num_decode_seqs=MAX_SEQS, variant=variant,
                       num_segments=16, interpret=False,
                   )).lower(*(a[n] for n in ("q", "kv", "layer", "pt", "ctx",
                                             "qsl", "qlens")))


BF16, F32 = jnp.bfloat16, jnp.float32
KERNEL_CASES = {
    "decode-baseline": (QWEN, BF16, "decode", "baseline"),
    "decode-gqa": (QWEN, BF16, "decode", "gqa"),
    "decode-segmented": (QWEN, BF16, "decode", "segmented"),
    "prefill-qblock": (QWEN, BF16, "prefill", None),
    "unified-gqa": (QWEN, BF16, "unified", "gqa"),
    "unified-segmented": (QWEN, BF16, "unified", "segmented"),
    "unified-segmented-d64": (SMOLLM, BF16, "unified", "segmented"),
    # float32 pages contract at HIGHEST precision
    "unified-segmented-f32": (QWEN, F32, "unified", "segmented"),
}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_paged_attention_kernel_compiles_for_v5e(one_chip, case):
    heads, dtype, kind, variant = KERNEL_CASES[case]
    if kind == "decode":
        lowered = _decode(variant, _kernel_args(
            one_chip, heads, dtype, seqs=MAX_SEQS, tokens=MAX_SEQS))
    elif kind == "prefill":
        lowered = _prefill(_kernel_args(
            one_chip, heads, dtype, seqs=MAX_SEQS, tokens=CHUNK_TOKENS))
    else:
        lowered = _unified(variant, _kernel_args(
            one_chip, heads, dtype, seqs=2 * MAX_SEQS,
            tokens=MAX_SEQS + CHUNK_TOKENS))
    assert "tpu_custom_call" in lowered.compile().as_text()


def test_qwen_unified_step_compiles_for_v5e(one_chip, monkeypatch):
    """The whole packed step the engine launches for Qwen2.5-3B (36
    layers, bf16, fused sampling) with the Pallas kernels, at the pool and
    token bucket chip_smoke.py serves with."""
    # the described chip is not the backend: kernels would otherwise
    # lower in interpret mode
    monkeypatch.setattr(repro.kernels, "interpret_mode",
                        lambda interpret=None: False)
    cfg = ARCHS["qwen2.5-3b"]
    sh = one_chip
    place = lambda t: jax.tree.map(  # noqa: E731
        lambda x: _spec(sh, x.shape, x.dtype), t)
    params = place(M.init_abstract(cfg))
    cache = place(M.make_cache_specs(cfg, max_seqs=MAX_SEQS,
                                     num_pages=PAGES))
    t, s = MAX_SEQS + CHUNK_TOKENS, 2 * MAX_SEQS
    i32, f32 = jnp.int32, jnp.float32
    batch = {
        "inputs": (1, t), "positions": (1, t), "slot_mapping": (1, t),
        "token_source": (1, t), "page_table": (s, PAGES_PER_SEQ),
        "context_lens": (s,), "query_lens": (s,), "query_start_loc": (s + 1,),
        "top_k": (s,), "stream_ids": (s,), "num_generated": (s,),
        "prev_tokens": (s,),
    }
    batch = {k: _spec(sh, v, i32) for k, v in batch.items()}
    batch["temperature"] = _spec(sh, (s,), f32)
    batch["top_p"] = _spec(sh, (s,), f32)
    step = jax.jit(functools.partial(
        M.apply_unified, cfg, backend="pallas",
        kernel_cfg=KernelConfig("segmented", num_segments=16),
        num_decode_seqs=MAX_SEQS, sample=True))
    compiled = step.lower(params, cache, batch).compile()
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert total < HBM_BYTES, ma


# Each benchmark cell's geometry (bench/configs/<arch>.json "engine"): the
# KV slots, pages of 128 tokens, the longest context, a 512-token chunk,
# and the kernel configuration its packed step dispatches most.
CELLS = {
    "qwen2.5-3b": (32, 770, 8192, KernelConfig("segmented", num_segments=16)),
    "smollm-135m": (128, 2056, 2048, KernelConfig("gqa")),
}
# Instructions whose result may be pool-sized: the arguments, tuple
# plumbing, the layer loop, free reshapes, and the Pallas kernels.
POOL_SIZED_OK = {"parameter", "tuple", "get-tuple-element", "while",
                 "bitcast"}
_HLO_RESULT = re.compile(
    r"^\s*(?:ROOT\s+)?%\S+\s*=\s*(\w+)\[([\d,]*)\]\S*\s+([\w-]+)\(")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
          "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
          "u64": 8}


def _pool_sized(hlo_text: str, limit: int) -> list[str]:
    """HLO lines outside POOL_SIZED_OK whose array result holds at least
    `limit` bytes, in the computations that own buffers: the entry and the
    loop bodies, not the bodies of fusions (a fusion's inner values live in
    registers and VMEM, its result is the buffer)."""
    fused = set(re.findall(r"\bfusion\(.*?calls=%([\w.-]+)", hlo_text))
    out, comp = [], None
    for line in hlo_text.splitlines():
        head = re.match(r"^(?:ENTRY\s+)?%([\w.-]+)\s.*\{\s*$", line)
        if head:
            comp = head.group(1)
            continue
        m = _HLO_RESULT.match(line)
        if not m or comp in fused:
            continue  # tuple-shaped results, non-instruction lines
        dtype, dims, opcode = m.groups()
        size = _BYTES.get(dtype, 4) * math.prod(
            int(d) for d in dims.split(",") if d)
        if size < limit or opcode in POOL_SIZED_OK:
            continue
        if opcode == "custom-call" and "tpu_custom_call" in line:
            continue
        out.append(line.strip()[:200])
    return out


@pytest.mark.parametrize("arch", sorted(CELLS))
def test_unified_step_updates_pool_in_place(one_chip, monkeypatch, arch):
    """The packed step at a benchmark cell's geometry, cache donated as
    the engine's executor donates it.  No instruction but the kernels and
    the loop plumbing yields a result as large as one layer's K pool (no
    slice, pad, relayout or rewrite of a layer), the compile aliases the
    whole pool to the output, and it needs less scratch than one layer's
    K pool.  The fused sampler is left out: it never touches the pool;
    `test_sampler_has_no_sort` bounds its scratch alone, and
    `test_qwen_unified_step_compiles_for_v5e` compiles the step with it."""
    monkeypatch.setattr(repro.kernels, "interpret_mode",
                        lambda interpret=None: False)
    max_seqs, pages, max_len, kcfg = CELLS[arch]
    cfg = ARCHS[arch].replace(page_size=128)
    sh = one_chip
    place = lambda t: jax.tree.map(  # noqa: E731
        lambda x: _spec(sh, x.shape, x.dtype), t)
    params = place(M.init_abstract(cfg))
    cache = place(M.make_cache_specs(cfg, max_seqs=max_seqs,
                                     num_pages=pages))
    t, s = max_seqs + CHUNK_TOKENS, 2 * max_seqs
    batch = {
        "inputs": (1, t), "positions": (1, t), "slot_mapping": (1, t),
        "token_source": (1, t), "page_table": (s, max_len // 128),
        "context_lens": (s,), "query_lens": (s,), "query_start_loc": (s + 1,),
        "prev_tokens": (s,),
    }
    batch = {k: _spec(sh, v, jnp.int32) for k, v in batch.items()}
    step = jax.jit(functools.partial(
        M.apply_unified, cfg, backend="pallas", kernel_cfg=kcfg,
        num_decode_seqs=max_seqs), donate_argnums=(1,))
    compiled = step.lower(params, cache, batch).compile()
    layer_k_bytes = (cfg.num_kv_heads * pages * 128 * cfg.resolved_head_dim
                     * jnp.dtype(cfg.param_dtype).itemsize)
    big = _pool_sized(compiled.as_text(), layer_k_bytes)
    assert not big, "\n".join(big)
    pool_bytes = sum(x.size * x.dtype.itemsize
                     for x in jax.tree.leaves(cache["attn"]))
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= pool_bytes, ma
    assert ma.temp_size_in_bytes < layer_k_bytes, ma


@pytest.mark.parametrize("arch", sorted(CELLS))
def test_sampler_has_no_sort(one_chip, arch):
    """The fused sampler alone at a benchmark cell's `[2 x max_seqs, V]`
    float32 logits: no `sort` instruction (its top-k and top-p cuts are
    bisected), and less scratch than one `[S, V]` float32 array, where the
    sorting sampler needed 84 MB (Qwen) and 102 MB (SmolLM): the filtered
    rows are recomputed from the logits in each pass, never stored."""
    s, v = 2 * CELLS[arch][0], ARCHS[arch].vocab_size
    sh = one_chip
    f32, i32 = jnp.float32, jnp.int32
    keys = jax.ShapeDtypeStruct((s,), jax.random.key(0).dtype, sharding=sh)
    compiled = jax.jit(M.sampling.sample_tokens).lower(
        _spec(sh, (s, v), f32), _spec(sh, (s,), f32), _spec(sh, (s,), f32),
        _spec(sh, (s,), i32), keys).compile()
    sorts = re.findall(r"^.*\bsort\(.*$", compiled.as_text(), re.M)
    assert not sorts, "\n".join(line.strip()[:200] for line in sorts)
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes < s * v * 4, ma
