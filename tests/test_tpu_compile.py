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


# (Hq, Hkv, D): Qwen2.5-3B, and SmolLM's head_dim 64, which the ops pad
# to 128 lanes
QWEN = (16, 2, 128)
SMOLLM = (9, 3, 64)
MAX_SEQS, PAGES, PAGE, PAGES_PER_SEQ, CHUNK_TOKENS = 8, 2048, 16, 128, 512


def _kernel_args(sh, heads, dtype, *, seqs, tokens):
    hq, hkv, d = heads
    i32 = jnp.int32
    pages = _spec(sh, (hkv, PAGES, PAGE, d), dtype)
    return dict(
        q=_spec(sh, (tokens, hq, d), dtype), k=pages, v=pages,
        pt=_spec(sh, (seqs, PAGES_PER_SEQ), i32),
        ctx=_spec(sh, (seqs,), i32),
        qsl=_spec(sh, (seqs + 1,), i32),
        qlens=_spec(sh, (seqs,), i32))


def _decode(variant, a):
    return jax.jit(lambda q, k, v, pt, ctx: ops.paged_attention_decode(
        q, k, v, pt, ctx, variant=variant, num_segments=8, interpret=False,
    )).lower(a["q"], a["k"], a["v"], a["pt"], a["ctx"])


def _prefill(a):
    return jax.jit(lambda q, k, v, pt, ctx, qsl, ql:
                   ops.paged_attention_prefill(
                       q, k, v, pt, ctx, qsl, ql, block_q=16,
                       interpret=False,
                   )).lower(*(a[n] for n in ("q", "k", "v", "pt", "ctx",
                                             "qsl", "qlens")))


def _unified(variant, a):
    return jax.jit(lambda q, k, v, pt, ctx, qsl, ql:
                   ops.paged_attention_unified(
                       q, k, v, pt, ctx, qsl, ql, num_decode_seqs=MAX_SEQS,
                       variant=variant, num_segments=16, interpret=False,
                   )).lower(*(a[n] for n in ("q", "k", "v", "pt", "ctx",
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
