"""Microbenchmark framework (paper §5.2, Fig. 5 left half).

Runs OUTSIDE the serving runtime: generates realistic request mixes
(variable context/query lengths, decode shares — §7.1) and measures each
kernel configuration. On TPU it times the real Pallas kernels; on a CPU
host it evaluates the analytic cost model (costmodel.py) so the tuning
WORKFLOW — sweep, compare, export heuristics — is identical.
"""
from __future__ import annotations

import dataclasses
import itertools
import time

import numpy as np

from repro.autotune.costmodel import (
    Scenario, decode_time, prefill_time, split_phases, unified_time,
)
from repro.core.attention.heuristics import KernelConfig


# the Llama3-8B-flavored default geometry shared by scenario_grid and the
# chunk-size roofline in tune_and_export (one source of truth)
ARCH_DEFAULTS = {"num_q_heads": 32, "num_kv_heads": 8, "head_dim": 128,
                 "page_size": 16}


def scenario_grid(*, num_q_heads=ARCH_DEFAULTS["num_q_heads"],
                  num_kv_heads=ARCH_DEFAULTS["num_kv_heads"],
                  head_dim=ARCH_DEFAULTS["head_dim"],
                  page_size=ARCH_DEFAULTS["page_size"],
                  seed=0) -> list[Scenario]:
    """The paper's Llama3-8B-flavored sweep: batch sizes x max sequence
    lengths x decode shares, with per-request length jitter."""
    rng = np.random.default_rng(seed)
    out = []
    for bs, max_len, dshare in itertools.product(
        (1, 4, 16, 64, 128), (128, 1024, 8192, 32768), (0.0, 0.5, 1.0)
    ):
        ctx = rng.integers(max(max_len // 4, 16), max_len + 1, size=bs)
        n_dec = int(round(bs * dshare))
        qlens = np.ones(bs, np.int64)
        if bs - n_dec:
            qlens[n_dec:] = np.minimum(
                ctx[n_dec:], rng.integers(64, 2048, size=bs - n_dec)
            )
        out.append(Scenario(
            num_seqs=bs, context_lens=tuple(int(c) for c in ctx),
            query_lens=tuple(int(q) for q in qlens),
            num_q_heads=num_q_heads, num_kv_heads=num_kv_heads,
            head_dim=head_dim, page_size=page_size,
        ))
    return out


DECODE_SPACE: list[KernelConfig] = [
    KernelConfig("baseline"),
    *[KernelConfig("gqa", tile=t) for t in (8, 16)],
    *[KernelConfig("segmented", tile=t, num_segments=s)
      for t in (8, 16) for s in (2, 4, 8, 16)],
]

PREFILL_SPACE: list[KernelConfig] = [
    KernelConfig("gqa", tile=t, block_q=bq)
    for t in (8, 16) for bq in (8, 16, 32, 64)
]

# the unified launch tunes both regions at once: the decode-region variant
# (C1-C3) x the chunk-region Q-block size, over a shared tile
UNIFIED_SPACE: list[KernelConfig] = [
    *[KernelConfig("gqa", tile=t, block_q=bq)
      for t in (8, 16) for bq in (8, 16, 32)],
    *[KernelConfig("segmented", tile=16, num_segments=s, block_q=bq)
      for s in (4, 8) for bq in (16, 32)],
]


def measure(scenario: Scenario, cfg: KernelConfig, *,
            use_hardware: bool = False, unified: bool = False) -> float:
    """Latency (s) of this config on this scenario.

    Padded engine (`unified=False`): a mixed batch runs as two launches
    (one decode, one prefill executable), so the scenario is split by
    phase (q == 1 vs q > 1) and each sub-batch is costed/timed against its
    own launch only — costing the whole scenario in both phases would
    double-count every sequence's context.

    Packed engine (`unified=True`): the mixed batch IS the launch — the
    whole scenario is costed as one token-packed dispatch
    (costmodel.unified_time), which is what the unified tree is fit on."""
    if use_hardware:  # pragma: no cover - TPU-only path
        if unified:
            return _measure_unified_on_device(scenario, cfg)
        dec, pre = split_phases(scenario)
        return sum(_measure_on_device(sub, cfg)
                   for sub in (dec, pre) if sub is not None)
    tile = cfg.tile or scenario.page_size
    if unified:
        return unified_time(scenario, variant=cfg.variant, tile=tile,
                            num_segments=cfg.num_segments,
                            block_q=cfg.block_q)
    dec, pre = split_phases(scenario)
    t = 0.0
    if dec is not None:
        t += decode_time(dec, variant=cfg.variant, tile=tile,
                         num_segments=cfg.num_segments)
    if pre is not None:
        t += prefill_time(pre, block_q=cfg.block_q, tile=tile)
    return t


def _measure_on_device(scenario: Scenario, cfg: KernelConfig,
                       warmup: int = 20, iters: int = 100) -> float:
    """Wall-clock timing of the real kernels (paper §7.1 methodology:
    20 warmup + mean of 100).  Expects a single-phase scenario (see
    `measure`): all-decode batches time the decode kernel, batches with
    query_lens > 1 time the Q-Block prefill kernel.  The pool holds one
    layer of the serving layout: a K|V row per token and KV head."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.paged_attention import ops

    s = scenario
    np_ = -(-s.max_context // s.page_size)
    p = s.num_seqs * np_ + 1
    kk, kq = jax.random.split(jax.random.key(0))
    pool = jax.random.normal(
        kk, (1, s.num_kv_heads, p, s.page_size, 2 * s.head_dim),
        jnp.bfloat16)
    pt = jnp.arange(1, 1 + s.num_seqs * np_,
                    dtype=jnp.int32).reshape(s.num_seqs, np_)
    ctx = jnp.asarray(s.context_lens, jnp.int32)
    is_prefill = any(q > 1 for q in s.query_lens)

    if is_prefill:
        total_q = sum(s.query_lens)
        q = jax.random.normal(kq, (total_q, s.num_q_heads, s.head_dim),
                              jnp.bfloat16)
        qsl = jnp.asarray(np.concatenate(
            [[0], np.cumsum(s.query_lens)]), jnp.int32)
        qlens = jnp.asarray(s.query_lens, jnp.int32)

        def run():
            return ops.paged_attention_prefill(
                q, pool, 0, pt, ctx, qsl, qlens, block_q=cfg.block_q,
                tile=cfg.tile)
    else:
        q = jax.random.normal(kq, (s.num_seqs, s.num_q_heads, s.head_dim),
                              jnp.bfloat16)

        def run():
            return ops.paged_attention_decode(
                q, pool, 0, pt, ctx, variant=cfg.variant, tile=cfg.tile,
                num_segments=cfg.num_segments)

    for _ in range(warmup):
        run().block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        run().block_until_ready()
    return (time.perf_counter() - t0) / iters


def _measure_unified_on_device(scenario: Scenario, cfg: KernelConfig,
                               warmup: int = 20, iters: int = 100) -> float:
    """Wall-clock timing of the REAL packed launch
    (`ops.paged_attention_unified`) on a mixed scenario — the engine's
    packed layout: q == 1 sequences first (the static decode region),
    chunks behind them.  This is what the unified tree must be fit to on
    hardware; summing separate per-phase kernel timings would miss the
    packed launch's own behavior."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.paged_attention import ops

    pairs = sorted(zip(scenario.context_lens, scenario.query_lens),
                   key=lambda cq: cq[1] > 1)  # decode rows first
    s = dataclasses.replace(
        scenario, context_lens=tuple(c for c, _ in pairs),
        query_lens=tuple(q for _, q in pairs))
    nd = sum(1 for q in s.query_lens if q == 1)
    np_ = -(-s.max_context // s.page_size)
    p = s.num_seqs * np_ + 1
    kk, kq = jax.random.split(jax.random.key(0))
    pool = jax.random.normal(
        kk, (1, s.num_kv_heads, p, s.page_size, 2 * s.head_dim),
        jnp.bfloat16)
    pt = jnp.arange(1, 1 + s.num_seqs * np_,
                    dtype=jnp.int32).reshape(s.num_seqs, np_)
    ctx = jnp.asarray(s.context_lens, jnp.int32)
    total_q = sum(s.query_lens)
    q = jax.random.normal(kq, (total_q, s.num_q_heads, s.head_dim),
                          jnp.bfloat16)
    qsl = jnp.asarray(np.concatenate(
        [[0], np.cumsum(s.query_lens)]), jnp.int32)
    qlens = jnp.asarray(s.query_lens, jnp.int32)

    def run():
        return ops.paged_attention_unified(
            q, pool, 0, pt, ctx, qsl, qlens, num_decode_seqs=nd,
            variant=cfg.variant, tile=cfg.tile,
            num_segments=cfg.num_segments, block_q=cfg.block_q)

    for _ in range(warmup):
        run().block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        run().block_until_ready()
    return (time.perf_counter() - t0) / iters


@dataclasses.dataclass
class SweepResult:
    scenario: Scenario
    timings: dict[int, float]  # config index -> seconds

    def best(self, space) -> KernelConfig:
        idx = min(self.timings, key=self.timings.get)
        return space[idx]


def sweep(scenarios, space, *, use_hardware=False,
          unified=False) -> list[SweepResult]:
    out = []
    for sc in scenarios:
        timings = {
            i: measure(sc, cfg, use_hardware=use_hardware, unified=unified)
            for i, cfg in enumerate(space)
        }
        out.append(SweepResult(sc, timings))
    return out
