"""Continuous-batching inference engine (the vLLM-v1 analog, paper Fig. 1-2).

Unified token-packed step (`packed_attention=True`, the default for
attention-family models — the paper's headline design): every scheduled
piece of work — decode rows (q = 1), fresh prefill chunks, and
resumed/cached-prefill chunks — is packed into ONE [1, T] token stream and
executed by ONE `unified` executable per step, the serving-loop analog of
the paper's single variable-length-batch kernel launch.  The packed layout
is:

    token row   0 .. max_seqs-1    the STATIC decode region: one row per
                                   batch slot (paper C5), dead slots
                                   masked by context_lens == 0
    token row   max_seqs .. T-1    prefill chunks back-to-back, bucketed
                                   to a power-of-two total-token count

with ragged metadata (`query_start_loc` / `query_lens` / `context_lens`,
paper §6.1) plus a per-token `slot_mapping` for the KV page writes and
per-token absolute positions for packed-position RoPE.  Fresh and resumed
chunks are the SAME thing here (a chunk is just `context_lens >
query_lens` when it has prior context), so the three executable families
of the padded path collapse into one: `compile_events` grows per
(token-bucket x KernelConfig) — the sequence axis and page-table width
are static — instead of per kind x batch x seq buckets, and no FLOPs are
spent on [B, S] padding.  The padded per-kind path is kept behind
`Engine(packed_attention=False)` — it is the
differential baseline (tests/test_unified_attention.py proves packed ==
padded token-for-token) and the fallback for SSM/hybrid/MLA families,
whose recurrent or latent state is not page-addressable per token.

Fused packed sampling (`fused_sampling=True`, default on the packed
path): the per-seq last-token gather AND sampling (greedy / temperature /
top-k / top-p, per-request params, per-request PRNG streams — see
models/sampling.py) run INSIDE the unified executable, so a steady-state
packed step is exactly ONE device dispatch and the only device->host
transfer is [S] sampled token ids — the full [S, V] logits never cross
the bus (only behind `debug_logits=True`).  `fused_sampling=False` keeps
the packed attention launch but samples in a second `_sample_fn`
dispatch — the two-dispatch differential baseline the `fused-sampling`
bench scenario compares against.  The padded per-kind path always
two-dispatches.

Async double-buffered serving (`submit()` / `stream()` / `run()`): the
synchronous `step()` is retained unchanged, but the streaming loop
overlaps host and device — step N+1 is scheduled, packed, and DISPATCHED
before step N's sampled tokens are pulled from the device.  Decode rows
whose input token is still in flight read it device-side
(`prev_tokens[token_source]` inside the executable); host-side, a
PENDING_TOKEN placeholder holds the output position so lengths, paging,
and max_new_tokens bookkeeping stay exact, and EOS/finish processing
simply lands one step late (a speculatively scheduled row of a request
that finished or was preempted in flight is discarded by its
`_spec_epoch`).  Telemetry records the host work that overlapped device
execution as `overlap` phase spans.

Phase spans (`serving/spans.py`): each host phase of a step runs under a
`repro.<phase>` profiler annotation carrying the step's id — schedule,
pack, compile, dispatch, prefix_index, wait_tokens, consume, finish —
always on and without a host sync, so a profile puts every idle stretch
of the device beside the host phase that held it.

Static-shape discipline = the TPU analog of CUDA-graph capture (paper §6.2):
every jitted executable is keyed by its bucket tuple; the packed path
buckets on the pow2 total-token count alone, the padded path on
per-kind (batch, seq) buckets — either way a steady-state serve loop
replays a handful of compiled programs and never recompiles.
`Engine.compile_events` counts captures, mirroring vLLM's
one-graph-per-batch-size policy; `Engine.launched_token_slots` counts the
token rows actually launched (the padding-waste observable the
`padding-waste` benchmark scenario reports).

Metadata computation (paper §6.1) happens host-side in numpy: page tables,
context lens, query lens, query start locs, slot mappings; nothing
shape-dynamic crosses into the compiled functions.

Prefix caching (`enable_prefix_caching=True`): the allocator is ref-counted
and a content-addressed `PrefixCache` indexes every full written page by its
hash-chained key. Admission reuses the longest cached prefix and
embeds/computes ONLY the uncached suffix while attending over the full
paged context (context_lens = cached + chunk).  Attention-family models
only; outputs are equivalent to the uncached engine while prefilling
strictly fewer tokens.

Chunked prefill (`enable_chunked_prefill=True`): the scheduler splits long
prompts into token-budget-sized chunks across consecutive steps; a chunk
with `chunk_start > 0` — whether its context comes from an earlier chunk
or from a prefix-cache hit — simply resumes at that context.  Chunking
only changes WHEN prompt tokens are computed, never WHAT is computed:
outputs are token-for-token identical to the unchunked engine
(tests/test_chunked_prefill.py proves it differentially).

Kernel-config dispatch (paper §5/§6.2, Fig. 5): every step builds a
host-side `BatchProfile` from the scheduled batch's metadata — including
`total_tokens` and the decode/prefill mix for packed batches — and asks
the heuristics trees (`unified_config` / `decode_config` /
`prefill_config`, autotune-exported via `heuristics.load()` /
$REPRO_ATTN_HEURISTICS, or the paper-shaped defaults) for a
`KernelConfig`.  The chosen config is STATIC: executables are keyed by
(kind, buckets, KernelConfig), so a tree that flips variants by batch
shape replays the already-captured graph for that config instead of
thrashing `compile_events`.  Profile lengths are bucketed to powers of two
before tree lookup so the set of distinct configs — and hence captures —
stays bounded.  Per-step choices surface in `step()` stats (`dispatch`)
and cumulatively in `Engine.dispatch_counts`.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import logging
import math
import time
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.attention import heuristics
from repro.core.paged.allocator import RefCountedPageAllocator
from repro.models import model as M
from repro.serving.executor import make_executor
from repro.serving.prefix_cache import PrefixCache
from repro.serving.request import PENDING_TOKEN, Request, State
from repro.serving.scheduler import Scheduler
from repro.serving.spans import Span
from repro.utils.misc import cdiv, next_power_of_2

log = logging.getLogger(__name__)

_SSM_CACHE_KEYS = ("mamba", "mlstm", "slstm")  # slot-indexed (axis 1) caches


@dataclasses.dataclass
class _PackedLaunch:
    """Host-side record of one unified launch: which request gets which
    sampled row back, plus what the two-dispatch sampler needs."""
    # (Request, packed row index, request._spec_epoch at pack time) for
    # every row that SAMPLES — decode rows and prompt-completing chunks.
    # The epoch lets the async loop discard rows whose request was
    # preempted while the launch was in flight.
    rows: list[tuple[Request, int, int]]
    prefill_reqs: list[Request]
    profile: heuristics.BatchProfile
    kcfg: heuristics.KernelConfig | None
    tokens: int  # launched token-bucket width
    # per-row (temps, top_p, top_k, streams, num_generated) numpy arrays:
    # the fused launch's sampling inputs, the host-side `_sample_fn`'s, and
    # what the sampler counters count
    sampling: tuple | None = None
    # speculative decoding: packed row index -> number of DRAFT tokens
    # verified in that row (the row emits 1..drafts+1 tokens); empty on
    # non-speculative launches
    spec: dict[int, int] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class _Inflight:
    """One dispatched-but-not-yet-consumed engine step (the double-buffer
    slot of the async loop)."""
    dec: object  # scheduler decision
    stats: dict
    t0: float  # telemetry clock at the step's start (0.0 without)
    step: int  # the step's id: the step_idx it has when it finishes
    pack: _PackedLaunch | None = None
    out: object = None  # device [S] sampled ids (fused) or [S, V] logits


class Engine:
    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        max_seqs: int = 8,
        num_pages: int = 128,
        max_model_len: int = 2048,
        max_prefill_tokens: int | str = 8192,
        backend: str = "xla",
        packed_attention: bool = True,
        fused_sampling: bool = True,
        debug_logits: bool = False,
        enable_prefix_caching: bool = False,
        enable_chunked_prefill: bool = False,
        seed: int = 0,
        telemetry=None,
        refit=None,
        tp: int = 1,
        speculative: bool = False,
        draft_k: int = 4,
        spec_config=None,
    ):
        self.cfg = cfg
        self.backend = backend
        self.tp = tp
        # obs.Telemetry | None.  None (the default) disables every hook
        # AND the block_until_ready timing barriers — the serving loop
        # stays exactly as asynchronous as before.
        self.telemetry = telemetry
        # obs.RefitDaemon | None: after every finished step the engine
        # applies any pending heuristics hot-swap (and, in the daemon's
        # inline mode, evaluates its refit trigger) — swaps only ever
        # happen BETWEEN steps, so a step never sees two trees.
        self.refit = refit
        if refit is not None:
            assert telemetry is not None and refit.telemetry is telemetry, \
                "refit daemon must watch this engine's telemetry"
        if telemetry is not None:
            telemetry.set_arch(
                num_q_heads=cfg.num_q_heads,
                num_kv_heads=max(cfg.num_kv_heads, 1),
                head_dim=cfg.resolved_head_dim,
                page_size=cfg.page_size,
                tp=tp)
        self.max_seqs = max_seqs
        self.num_pages = num_pages
        self.pages_per_seq = cdiv(max_model_len, cfg.page_size)
        # $REPRO_ATTN_HEURISTICS installs an autotune-exported tree before
        # the first dispatch (idempotent across engine constructions)
        env_tree = heuristics.maybe_load_env()
        if env_tree:
            log.info("engine: attention heuristics from %s", env_tree)
        # kernel-config dispatch only pays off where the trees actually
        # steer a paged-attention kernel: GQA-style attention families
        # (MLA decodes through a fixed absorbed-form path; SSM families
        # have no attention cache at all)
        self._dispatch_enabled = (
            M.attn_layer_count(cfg) > 0 and not cfg.mla.kv_lora_rank)
        # the unified token-packed step needs every layer's context to be
        # page-addressable per token: attention families only (SSM/hybrid
        # recurrent state is slot-indexed; MLA decodes through the fixed
        # absorbed-form path).  Unsupported families silently fall back to
        # the padded per-kind path.
        self._packed = packed_attention and \
            cfg.family in ("dense", "moe", "audio", "vlm") and \
            not cfg.mla.kv_lora_rank
        if packed_attention and not self._packed:
            log.info("engine: packed attention unavailable for "
                     "family=%r/MLA; using the padded per-kind step",
                     cfg.family)
        # fused sampling rides inside the unified executable, so it is a
        # packed-path feature; elsewhere the host `_sample_fn` dispatch
        # remains (same math — see models/sampling.py)
        self._fused = fused_sampling and self._packed
        self._debug_logits = debug_logits
        if fused_sampling and not self._packed:
            log.info("engine: fused sampling needs the packed step; "
                     "using the two-dispatch sampler")
        # speculative decoding (n-gram drafts verified in the one packed
        # launch — serving/draft.py, docs/serving.md): the verify +
        # accept/reject + bonus-sample epilogue lives next to fused
        # sampling inside the unified executable, so it requires the
        # fused packed path
        self._spec = bool(speculative) and self._fused
        if speculative and not self._spec:
            log.info("engine: speculative decoding needs the fused packed "
                     "step; running non-speculative")
        self.drafter = None
        self.max_draft = 0
        if self._spec:
            from repro.serving.draft import Drafter, SpecConfig
            scfg = spec_config or SpecConfig(max_draft=max(1, draft_k))
            self.drafter = Drafter(scfg)
            self.max_draft = scfg.max_draft
        # cumulative speculative counters (per-step values land in step
        # stats): proposed drafts, accepted drafts, tokens emitted from
        # spec rows, and steps that carried at least one spec row
        self.spec_stats = {"proposed": 0, "accepted": 0, "emitted": 0,
                           "steps": 0}
        self._step_spec = (0, 0, 0)  # (proposed, accepted, emitted)/step
        # cumulative sampler counters (per-step row counts land in packed
        # step stats under "sampler"): packed launches that ran the draw,
        # the top-p search and the top-k search
        self.sampler_stats = {"draw": 0, "top_p": 0, "top_k": 0}
        self.seed = seed
        # mesh-aware launch layer: places params/cache and builds the
        # unified executables.  tp=1 degenerates to the pre-executor jit
        # partial (bit-identical); tp>1 runs the packed step under
        # shard_map with the KV pool split on the head axis.
        self.executor = make_executor(
            cfg, backend=backend, tp=tp, max_seqs=max_seqs,
            fused=self._fused, seed=seed, debug_logits=debug_logits,
            packed=self._packed, max_draft=self.max_draft)
        self.params = self.executor.place_params(params)
        self._group = max(1, cfg.num_q_heads // max(cfg.num_kv_heads, 1))
        self.dispatch_counts: collections.Counter = collections.Counter()
        self._last_dispatch: dict[str, dict] = {}
        if max_prefill_tokens == "auto":
            # chunk-size autotuner: per-step budget from the cost-model
            # decode-latency roofline (tuned-tree export overrides)
            from repro.autotune.costmodel import suggest_max_prefill_tokens
            max_prefill_tokens = (
                heuristics.suggested_max_prefill_tokens()
                or suggest_max_prefill_tokens(
                    num_q_heads=cfg.num_q_heads,
                    num_kv_heads=max(cfg.num_kv_heads, 1),
                    head_dim=cfg.resolved_head_dim,
                    page_size=cfg.page_size, max_seqs=max_seqs,
                    target_context=max_model_len))
            if not enable_chunked_prefill:
                # without chunking the budget gates MONOLITHIC admission:
                # a prompt longer than it would wait forever.  The roofline
                # chunk size only makes sense chunked; admit any resident
                # prompt instead.
                max_prefill_tokens = max(max_prefill_tokens, max_model_len)
            log.info("engine: autotuned max_prefill_tokens=%d",
                     max_prefill_tokens)
        self.max_prefill_tokens = max_prefill_tokens
        self.alloc = RefCountedPageAllocator(num_pages, cfg.page_size)
        self.prefix_cache = None
        if enable_prefix_caching or enable_chunked_prefill:
            assert cfg.family in ("dense", "moe", "audio", "vlm") \
                and not cfg.mla.kv_lora_rank, (
                    "prefix caching / chunked prefill need page-addressable "
                    f"context (unsupported for family={cfg.family!r}/MLA)")
        if enable_prefix_caching:
            self.prefix_cache = PrefixCache(self.alloc, cfg.page_size,
                                            telemetry=telemetry)
        self.sched = Scheduler(self.alloc, max_seqs=max_seqs,
                               max_prefill_tokens=max_prefill_tokens,
                               prefix_cache=self.prefix_cache,
                               enable_chunked_prefill=enable_chunked_prefill,
                               telemetry=telemetry, drafter=self.drafter)
        # the live cache: every launch DONATES the cache it is given and
        # returns the next one (the KV pool is updated in place), so
        # nothing may hold on to a launch's input after the launch
        self.cache = self.executor.place_cache(
            M.make_cache(cfg, max_seqs=max_seqs, num_pages=num_pages))
        # bytes of the KV pool on one device, and per unified executable
        # the input bytes its compile aliases to outputs (memory_analysis):
        # a steady engine shows every one aliasing the whole pool
        self.kv_pool_bytes = sum(
            math.prod(a.sharding.shard_shape(a.shape)) * a.dtype.itemsize
            for a in jax.tree.leaves(self.cache.get("attn")))
        self.cache_alias_bytes: dict[tuple, int | None] = {}
        self.page_table = np.zeros((max_seqs, self.pages_per_seq), np.int32)
        self.step_idx = 0
        self.prefilled_tokens = 0  # uncached tokens actually computed
        self.cached_prefill_tokens = 0  # tokens skipped via the prefix cache
        self.launched_token_slots = 0  # token rows launched (incl. padding)
        self.compile_events: list[tuple] = []  # (kind, b, s, kcfg)/capture
        # unified captures are compiled ahead of their first launch: the
        # compiled programs by executable-cache key (their text shows
        # which kernels run) and the seconds spent tracing + compiling
        self.executables: dict[tuple, jax.stages.Compiled] = {}
        self.compile_seconds = 0.0
        # device dispatches by kind ("unified" / "prefill" /
        # "prefill_cached" / "decode" / "sample"): the fused-sampling
        # acceptance tests assert a steady packed step adds exactly
        # {"unified": 1}
        self.device_calls: collections.Counter = collections.Counter()
        self._emitted: list[tuple[int, int]] = []  # (req_id, token)/step
        self.last_step_stats: dict | None = None
        self.last_step_logits = None  # device [S, V], debug_logits only
        # (copy of the cache, batch) the last unified launch read,
        # debug_logits only: what a replay of that step on another backend
        # needs (the launch itself consumed the original)
        self.last_launch = None
        self.last_generate: dict = {}  # drive-loop stats (see generate())
        self._compiled: dict[tuple, object] = {}
        # executable-cache key -> {"flops", "bytes_accessed"} | None:
        # XLA cost_analysis stamped once per capture (telemetry only)
        self._launch_costs: dict[tuple, dict | None] = {}

    # ------------------------------------------------------------------
    # compiled executables ("graphs")
    # ------------------------------------------------------------------

    def _get_fn(self, kind: str, b: int, s: int,
                kcfg: heuristics.KernelConfig | None = None):
        """Executable cache keyed by (kind, batch-bucket, seq-bucket,
        KernelConfig): the config is static dispatch metadata (kernel
        variant / tile / segments baked into the traced program), so a
        heuristics tree that switches variants by batch shape replays the
        capture for that config instead of re-tracing (`compile_events`
        grows one entry per bucket x config, never per step).  The config
        keys UNIFORMLY across backends — the xla decode path is
        variant-agnostic, so a flip there re-captures an equivalent
        program once; that bounded cost buys identical replay/stats
        semantics on both backends."""
        key = (kind, b, s, kcfg)
        if key not in self._compiled:
            self.compile_events.append(key)
            if kind.startswith("unified"):
                # the whole packed step: b = seq bucket, s = token bucket;
                # the static decode region (max_seqs rows) is part of the
                # traced program like the KernelConfig.  Fused-sampling
                # flags and the mesh placement are engine constants baked
                # into the executor's traced program — the cache key never
                # varies with them within one engine.
                self._compiled[key] = self.executor.build_unified(kcfg)
            else:
                apply = {"prefill": M.apply_prefill,
                         "prefill_cached": M.apply_prefill_cached,
                         "decode": M.apply_decode}[kind.split("/")[0]]
                # padded launches consume their cache like unified ones
                self._compiled[key] = jax.jit(
                    functools.partial(apply, self.cfg, backend=self.backend,
                                      kernel_cfg=kcfg),
                    donate_argnums=(1,))
        return self._compiled[key]

    # ------------------------------------------------------------------
    # phase spans and device-side cost
    # ------------------------------------------------------------------

    def _span(self, name: str, step: int, **kw) -> Span:
        return Span(name, step, self.telemetry, **kw)

    def _exe_cost(self, key: tuple, fn, *args) -> dict | None:
        """Best-effort XLA cost_analysis (flops / bytes accessed) of the
        executable behind `key`, memoized per executable-cache key.  A
        unified capture reuses its ahead-of-launch compile; the padded
        kinds lower+compile once per CAPTURE (after the launch was
        recorded, so it never pollutes launch timing; lowering reads only
        the shapes of `args`).  It lets warm
        launches stamp device-side cost into the latency grid — the refit
        can then split observed latency into a device-time floor and host
        overhead (`tune.refit_from_telemetry(separate_host_overhead=...)`)."""
        if key not in self._launch_costs:
            cost = None
            try:
                exe = self.executables.get(key) or fn.lower(*args).compile()
                ca = exe.cost_analysis()
                if ca:
                    cost = {
                        "flops": float(ca.get("flops", 0.0) or 0.0),
                        "bytes_accessed":
                            float(ca.get("bytes accessed", 0.0) or 0.0),
                    }
            except Exception as e:  # noqa: BLE001 — cost analysis is optional
                log.debug("cost_analysis unavailable for %s: %s", key[0], e)
            self._launch_costs[key] = cost
        return self._launch_costs[key]

    # ------------------------------------------------------------------
    # kernel-config dispatch (paper Fig. 5: profile -> tree -> config)
    # ------------------------------------------------------------------

    def _decode_profile(self, reqs: list[Request]) -> heuristics.BatchProfile:
        return heuristics.BatchProfile(
            num_seqs=len(reqs),
            max_context=next_power_of_2(max(r.total_len for r in reqs)),
            group=self._group, page_size=self.cfg.page_size,
            decode_share=1.0, avg_query_len=1,
            total_tokens=next_power_of_2(len(reqs)),
            tp=self.tp,
        )

    def _prefill_profile(self, reqs: list[Request]) -> heuristics.BatchProfile:
        max_ctx = max(r.chunk_start + r.num_scheduled_tokens for r in reqs)
        total = sum(r.num_scheduled_tokens for r in reqs)
        return heuristics.BatchProfile(
            num_seqs=len(reqs),
            max_context=next_power_of_2(max_ctx),
            group=self._group, page_size=self.cfg.page_size,
            decode_share=0.0,
            avg_query_len=next_power_of_2(max(total // len(reqs), 1)),
            total_tokens=next_power_of_2(total),
            tp=self.tp,
        )

    def _unified_profile(self, decode_reqs: list[Request],
                         prefill_reqs: list[Request],
                         spec_total: int = 0) \
            -> heuristics.BatchProfile:
        """Packed-batch profile: the mix features (`total_tokens`,
        `decode_share`, `avg_query_len`) describe the whole step, since
        the unified tree tunes the single launch covering both phases.
        `spec_total` (draft tokens verified this step) is its own bucketed
        dimension — speculative steps stretch decode rows into short
        chunks, a shape the tuned trees can split on."""
        nseq = len(decode_reqs) + len(prefill_reqs)
        total = len(decode_reqs) + spec_total \
            + sum(r.num_scheduled_tokens for r in prefill_reqs)
        max_ctx = max(
            [r.total_len + len(r.spec_tokens) for r in decode_reqs]
            + [r.chunk_start + r.num_scheduled_tokens
               for r in prefill_reqs])
        return heuristics.BatchProfile(
            num_seqs=nseq,
            max_context=next_power_of_2(max_ctx),
            group=self._group, page_size=self.cfg.page_size,
            decode_share=len(decode_reqs) / nseq,
            avg_query_len=next_power_of_2(max(total // nseq, 1)),
            total_tokens=next_power_of_2(total),
            spec_tokens=next_power_of_2(spec_total) if spec_total else 0,
            tp=self.tp,
        )

    def _dispatch(self, phase: str,
                  profile: heuristics.BatchProfile | None) \
            -> heuristics.KernelConfig | None:
        """Pick this launch's KernelConfig from the (loaded or default)
        tree and record it in the per-step / cumulative dispatch stats."""
        if not self._dispatch_enabled or profile is None:
            return None
        pick = {"decode": heuristics.decode_config,
                "unified": heuristics.unified_config}.get(
                    phase, heuristics.prefill_config)
        kcfg = heuristics.validate(pick(profile), self.cfg.page_size)
        self.dispatch_counts[(phase, kcfg.variant)] += 1
        if self.telemetry is not None:
            self.telemetry.record_dispatch(phase, kcfg.variant)
        self._last_dispatch[phase] = {
            "variant": kcfg.variant, "tile": kcfg.tile,
            "num_segments": kcfg.num_segments, "block_q": kcfg.block_q,
            "num_seqs": profile.num_seqs,
            "max_context": profile.max_context,
            "total_tokens": profile.total_tokens,
        }
        return kcfg

    @functools.cached_property
    def _sample_fn(self):
        """Host-side sampling dispatch (the padded path, and the packed
        path with `fused_sampling=False`): the SAME per-request-stream
        math as the fused in-graph sampler — one definition in
        models/sampling.py — so fused and two-dispatch engines with the
        same seed produce bit-identical tokens."""
        seed = self.seed

        def sample(logits, temperature, top_p, top_k, streams,
                   num_generated):
            keys = M.sampling.request_keys(seed, streams, num_generated)
            return M.sampling.sample_tokens(
                logits, temperature, top_p, top_k, keys)

        return jax.jit(sample)

    def _sampling_rows(self, n: int, fill: list[tuple[int, Request]]):
        """Per-row sampling-param arrays ([n] each) with neutral defaults
        on dead rows (temp 0 / top_p 1 / top_k 0 / stream 0 / drawn 0)."""
        temps = np.zeros((n,), np.float32)
        topp = np.ones((n,), np.float32)
        topk = np.zeros((n,), np.int32)
        streams = np.zeros((n,), np.int32)
        ngen = np.zeros((n,), np.int32)
        for i, r in fill:
            temps[i] = r.temperature
            topp[i] = r.top_p
            topk[i] = r.top_k
            streams[i] = r.sampling_stream
            # the draw counter must count IN-FLIGHT tokens too: a pending
            # placeholder is a drawn-but-not-yet-transferred token, and
            # this launch's draw comes after it
            ngen[i] = r.num_generated + (1 if r._placeholder else 0)
        return temps, topp, topk, streams, ngen

    def _count_sampler(self, pack: _PackedLaunch) -> dict:
        """The launch's rows that draw and that need each search, by the
        predicates the sampler's branches test (`filter_rows`); a branch
        runs when its count is nonzero."""
        temps, topp, topk, _, _ = pack.sampling
        rows = M.sampling.filter_rows(temps, topp, topk)
        sampled, top_p, top_k = (int(m.sum()) for m in rows)
        self.sampler_stats["draw"] += int(sampled > 0)
        self.sampler_stats["top_p"] += int(top_p > 0)
        self.sampler_stats["top_k"] += int(top_k > 0)
        return {"sampled_rows": sampled, "top_p_rows": top_p,
                "top_k_rows": top_k}

    def _host_tokens(self, out, pack: _PackedLaunch):
        """Block on a unified launch's result and return host token ids:
        the fused path just transfers the sampled ids ([S], or
        ([S, K+1] tokens, [S] num_emitted) under speculation); the
        two-dispatch path samples host-side from the [S, V] logits."""
        if self._spec:
            toks_d, emitted_d = out
            return np.asarray(toks_d), np.asarray(emitted_d)
        if self._fused:
            return np.asarray(out)
        self.device_calls["sample"] += 1
        temps, topp, topk, streams, ngen = pack.sampling
        return np.asarray(self._sample_fn(
            out, jnp.asarray(temps), jnp.asarray(topp), jnp.asarray(topk),
            jnp.asarray(streams), jnp.asarray(ngen)))

    def _emit_token(self, r: Request, tok: int) -> None:
        """Deliver one sampled token to a request: fill its pending
        placeholder (async) or append (sync), bump the RNG draw counter,
        and record the (req_id, token) pair for stream()."""
        if r._placeholder:
            r.output[-1] = tok
            r._placeholder = False
        else:
            r.output.append(tok)
        r.num_generated += 1
        self._emitted.append((r.req_id, tok))

    # ------------------------------------------------------------------
    # request API
    # ------------------------------------------------------------------

    def add_request(self, req: Request) -> None:
        assert req.num_prompt_tokens + req.max_new_tokens <= \
            self.pages_per_seq * self.cfg.page_size, "exceeds max_model_len"
        self.sched.add(req)

    def generate(self, requests: Sequence[Request],
                 max_steps: int = 10_000) -> list[Request]:
        for r in requests:
            self.add_request(r)
        steps = 0
        while self.sched.has_work and steps < max_steps:
            self.step()
            steps += 1
        self._note_drive_end("generate", steps, max_steps)
        return list(requests)

    def _note_drive_end(self, api: str, steps: int, max_steps: int) -> None:
        """Close out a drive loop: record its stats in
        `Engine.last_generate` and WARN if the step budget ran out with
        requests still unfinished — callers must not mistake truncated
        outputs for normal completion."""
        unfinished = len(self.sched.waiting) + len(self.sched.running)
        exhausted = unfinished > 0 and steps >= max_steps
        self.last_generate = {"steps": steps, "unfinished": unfinished,
                              "exhausted": exhausted}
        if exhausted:
            log.warning(
                "%s: max_steps=%d exhausted with %d request(s) not "
                "FINISHED — their outputs are truncated; raise max_steps "
                "or check Engine.last_generate", api, max_steps, unfinished)

    def submit(self, req: Request) -> int:
        """Queue a request for the streaming loop; returns the req_id
        that `stream()` tags its emitted tokens with."""
        self.add_request(req)
        return req.req_id

    def stream(self, *, max_steps: int = 10_000):
        """Drive the engine until the queue drains, yielding
        (req_id, token) pairs in emission order.

        On the packed path with fused sampling the loop is DOUBLE
        BUFFERED: each iteration schedules, packs, and DISPATCHES step
        N+1 before blocking on step N's sampled tokens, so host-side
        batch construction overlaps device execution (`overlap` phase
        spans in telemetry).  Other paths step synchronously — same
        yields, no overlap.  Speculative engines also step synchronously:
        step N's acceptance count decides step N+1's packed metadata
        (positions, context, pages), so there is nothing to pack before
        the tokens land — speculation buys its overlap inside the launch
        instead, emitting up to draft_k+1 tokens per dispatch."""
        steps = 0
        if not self._fused or self._spec:
            while self.sched.has_work and steps < max_steps:
                self.step()
                steps += 1
                yield from self._emitted
            self._note_drive_end("stream", steps, max_steps)
            return
        inflight: _Inflight | None = None
        while inflight is not None or \
                (self.sched.has_work and steps < max_steps):
            nxt = None
            if self.sched.has_work and \
                    steps + (1 if inflight is not None else 0) < max_steps:
                nxt = self._begin_step(inflight)
            if inflight is not None:
                self._finish_step(inflight)
                steps += 1
                yield from self._emitted
            inflight = nxt
        self._note_drive_end("stream", steps, max_steps)

    def run(self, *, max_steps: int = 10_000, on_token=None,
            on_finish=None) -> dict:
        """Always-on drive loop over `stream()`: consumes everything
        `submit()`ed (admissions during the loop included), invoking
        `on_token(req_id, token)` per sampled token and
        `on_finish(request)` as requests leave the batch.  Returns
        {"outputs": {req_id: [token, ...]}} merged with the
        `last_generate` drive stats."""
        outputs: dict[int, list[int]] = {}
        prev_cb = self.sched.on_finish
        if on_finish is not None:
            def chained(req):
                if prev_cb is not None:
                    prev_cb(req)
                on_finish(req)
            self.sched.on_finish = chained
        try:
            for rid, tok in self.stream(max_steps=max_steps):
                outputs.setdefault(rid, []).append(tok)
                if on_token is not None:
                    on_token(rid, tok)
        finally:
            self.sched.on_finish = prev_cb
        return {"outputs": outputs, **self.last_generate}

    # ------------------------------------------------------------------
    # one engine step
    # ------------------------------------------------------------------

    def _schedule_and_pack(self, step: int, prev_rows=None,
                           prev_out=None) -> _Inflight:
        """The front half of a step, shared by the synchronous `step()`
        and the async `_begin_step()`: schedule, account, update page
        tables, pack, and DISPATCH — no blocking on device results."""
        self._last_dispatch = {}
        with self._span("schedule", step) as sp:
            dec = self.sched.step(self.step_idx)
            sp.args.update(decode=len(dec.decode_reqs),
                           prefill=len(dec.prefill_reqs))
            new_tokens = dec.scheduled_prefill_tokens
            # cached tokens are reported on a request's FIRST chunk (the
            # one starting exactly at the matched prefix); later
            # chunk-resumes start past it and charge nothing
            cached_tokens = sum(r.num_cached_tokens for r in dec.prefill_reqs
                                if r.chunk_start == r.num_cached_tokens)
            self.prefilled_tokens += new_tokens
            self.cached_prefill_tokens += cached_tokens
            stats = {"prefill": len(dec.prefill_reqs),
                     "decode": len(dec.decode_reqs),
                     "preempted": len(dec.preempted),
                     "prefill_tokens": new_tokens,
                     "cached_tokens": cached_tokens,
                     "partial_prefills": sum(1 for r in dec.prefill_reqs
                                             if not r.prefill_done),
                     "budget_utilization": dec.budget_utilization}
            if self.prefix_cache is not None:
                stats.update(self.prefix_cache.stats())
            for req in dec.prefill_reqs:
                row = np.zeros((self.pages_per_seq,), np.int32)
                row[: len(req.pages)] = req.pages
                self.page_table[req.slot] = row
            for req in dec.decode_reqs:  # page growth
                row = self.page_table[req.slot]
                row[: len(req.pages)] = req.pages

        flight = _Inflight(dec=dec, stats=stats, t0=sp.t0, step=step)
        if self._packed and (dec.decode_reqs or dec.prefill_reqs):
            with self._span("pack", step) as sp:
                batch, pack = self._pack_unified(
                    dec.decode_reqs, dec.prefill_reqs,
                    prev_rows=prev_rows, prev_out=prev_out)
                sp.args["tokens"] = pack.tokens
            flight.out = self._launch_unified(batch, pack, step)
            flight.pack = pack
            stats["cache_alias_bytes"] = self.cache_alias_bytes[
                self._unified_key(pack)]
            stats["kv_pool_bytes"] = self.kv_pool_bytes
            stats["sampler"] = self._count_sampler(pack)
        if dec.prefill_reqs and self.prefix_cache is not None:
            with self._span("prefix_index", step):
                for r in dec.prefill_reqs:
                    # index the now-written full pages (up to this chunk's
                    # end) so concurrent shared-prefix requests can reuse
                    # them immediately — even mid-chunked-prefill; the
                    # cursor keeps the chained hashing O(prompt) overall
                    # (context_len is set at pack time, so this is safe to
                    # do while the launch is still in flight)
                    r.cache_cursor = self.prefix_cache.insert_incremental(
                        r.prompt, r.pages, r.context_len, r.cache_cursor)
        stats["dispatch"] = dict(self._last_dispatch)
        return flight

    def _finish_step(self, flight: _Inflight) -> dict:
        """The back half of a packed step: block on the launch's sampled
        tokens, fold them into request state, then close the step."""
        self._emitted = []
        stats = flight.stats
        self._step_spec = (0, 0, 0)
        if flight.pack is not None:
            with self._span("wait_tokens", flight.step, phase="sample"):
                toks = self._host_tokens(flight.out, flight.pack)
            with self._span("consume", flight.step):
                self._consume_unified(flight.pack, toks)
        if self._spec:
            p, a, e = self._step_spec
            stats["spec_proposed"] = p
            stats["spec_accepted"] = a
            stats["spec_emitted"] = e
        return self._close_step(flight)

    def step(self) -> dict:
        flight = self._schedule_and_pack(self.step_idx)
        if self._packed:
            return self._finish_step(flight)
        # padded per-kind path: run, then close the step (its launches
        # already consumed their results inline)
        self._emitted = []
        dec = flight.dec
        if dec.prefill_reqs:
            self._run_prefill(dec.prefill_reqs)
        if dec.decode_reqs:
            self._run_decode(dec.decode_reqs)
        return self._close_step(flight)

    def _close_step(self, flight: _Inflight) -> dict:
        """The end of every step, packed or padded: process finishes,
        snapshot the pool, close out stats and telemetry, and apply any
        pending heuristics hot-swap."""
        tel = self.telemetry
        stats = flight.stats
        with self._span("finish", flight.step, phase="host"):
            for req in list(self.sched.running):
                # a request whose LAST token is still in flight (unfilled
                # placeholder) must not finish yet — it finishes next
                # step, once the token lands
                if req.prefill_done and req.done and not req._placeholder:
                    slot = req.slot  # finish() releases the slot
                    self.sched.finish(req)
                    if slot is not None:
                        self.page_table[slot] = 0
            # pool occupancy AFTER finishes released their pages, so the
            # snapshot matches the harness's pages-conserved invariant
            stats["pool"] = self.alloc.mesh_stats(self.tp)
            stats["sampled_tokens"] = len(self._emitted)
            if tel:
                tel.record_step(t0=flight.t0, t1=tel.clock.now(),
                                decision=flight.dec, stats=stats,
                                engine=self)
            if self.refit is not None:  # hot-swap boundary (obs.refit)
                self.refit.on_step(self)
                stats["refit_swaps"] = self.refit.swaps
        self.step_idx += 1
        self.last_step_stats = stats
        return stats

    def _begin_step(self, prev: _Inflight | None) -> _Inflight:
        """Schedule + pack + dispatch step N+1 while step N (`prev`) is
        still executing on device.  Decode rows whose input token is in
        `prev`'s launch read it device-side via prev_tokens/token_source;
        host-side each such request gets a PENDING_TOKEN placeholder so
        every length / paging / max_new_tokens computation sees post-step
        state."""
        tel = self.telemetry
        # the host work below (schedule/pack/dispatch) runs while the
        # previous launch is still in flight
        overlap = tel.phase("overlap") if tel and prev is not None \
            and prev.pack is not None else contextlib.nullcontext()
        with overlap:
            prev_rows: dict[int, int] = {}
            if prev is not None and prev.pack is not None:
                for r, row, epoch in prev.pack.rows:
                    if r._spec_epoch != epoch or \
                            r.state not in (State.RUNNING, State.PREFILLING):
                        continue
                    prev_rows[r.req_id] = row
                    if not r._placeholder:
                        r.output.append(PENDING_TOKEN)
                        r._placeholder = True
            # step_idx counts finished steps; `prev` has not finished yet
            return self._schedule_and_pack(
                self.step_idx + (prev is not None), prev_rows=prev_rows,
                prev_out=prev.out if prev is not None else None)

    def _positions(self, pos: np.ndarray) -> jnp.ndarray:
        p = jnp.asarray(pos, jnp.int32)
        if self.cfg.rope_style == "mrope":
            p = jnp.broadcast_to(p[None], (3,) + p.shape)
        return p

    def _page_slots(self, row: np.ndarray, positions: np.ndarray) \
            -> np.ndarray:
        """Pool-local flat KV slots for in-sequence `positions` through one
        page-table row (host-side §6.1 metadata)."""
        ps = self.cfg.page_size
        return row[positions // ps] * ps + positions % ps

    def _pack_unified(self, decode_reqs: list[Request],
                      prefill_reqs: list[Request],
                      prev_rows: dict[int, int] | None = None,
                      prev_out=None) -> tuple[dict, _PackedLaunch]:
        """Build the batch for ONE token-packed launch (no dispatch).

        Layout: rows [0, max_seqs) are the static decode region — sequence
        i IS batch slot i, one token row each, dead slots masked by
        context_lens == 0 (so the decode region never changes shape and
        the steady decode-only state replays a single executable); prefill
        chunks (fresh AND resumed — a fresh chunk is just context ==
        query) pack back-to-back behind it, with the chunk-token count
        bucketed to a power of two.  The sequence axis is fully STATIC at
        2 * max_seqs (a step schedules at most max_seqs chunks; unused
        rows are dead, qlen = ctx = 0) and the page table full-width, so
        executables bucket ONLY on the token count — no per-chunk-count
        or per-context-depth fragmentation.  Only decode rows and
        prompt-completing chunks sample.

        `prev_rows` (async loop) maps req_id -> row in the STILL IN
        FLIGHT previous launch whose sampled token is this request's
        decode input: the packed batch routes it device-side through
        `prev_tokens` (= `prev_out`, the previous launch's [S] output)
        and `token_source`, so the host never waits for it.

        Decode-row ORDER within the decode region is free (every row
        carries its own page-table copy / positions / slot mapping, and
        `pack.rows` maps requests back to rows per launch), so plain
        decode rows are sorted by pow2 context-length bucket — rows with
        similar context depths group coherently for the kernel's page
        loops.  Speculative decode rows (requests carrying `spec_tokens`
        drafts) leave the decode region entirely: each packs as a resumed
        chunk of q = drafts+1 tokens [last real token, draft_1..draft_k]
        at absolute positions total_len-1.., behind the prefill chunks
        and likewise context-bucket sorted.  Same executable, no new
        launch kind — verification is the fused epilogue's job.

        Each request's `context_len` advances HERE (the KV its launch
        will write is determined at pack time) — consumers downstream of
        dispatch, like incremental prefix-cache indexing, see the
        post-step value without blocking on the device.  Spec rows record
        the GUARANTEED minimum (total_len: the last real token's KV is
        written unconditionally); `_consume_unified` finalizes it to
        cover exactly the accepted tokens and rolls the rest back."""
        ms = self.max_seqs
        ps = self.cfg.page_size
        bucket = lambda r: next_power_of_2(max(r.total_len, 1))
        plain = [r for r in decode_reqs if not r.spec_tokens]
        spec_reqs = [r for r in decode_reqs if r.spec_tokens]
        plain.sort(key=bucket)
        spec_reqs.sort(key=bucket)
        assert len(prefill_reqs) + len(spec_reqs) <= ms, \
            "chunk region overflow: scheduler must cap spec rows"
        spec_total = sum(len(r.spec_tokens) for r in spec_reqs)
        profile = self._unified_profile(decode_reqs, prefill_reqs,
                                        spec_total=spec_total)
        n_pref = sum(r.num_scheduled_tokens for r in prefill_reqs) \
            + spec_total + len(spec_reqs)
        t = ms + (max(next_power_of_2(n_pref), ps) if n_pref else 0)
        s = 2 * ms
        # static FULL-width page table (paper C5, like the padded decode
        # path): dead tiles are masked in-kernel, so executables bucket
        # ONLY on the token count — context growth never recompiles
        np_b = self.pages_per_seq
        trash = self.num_pages * ps  # out-of-range slot: writes dropped

        tokens = np.zeros((1, t), np.int32)
        pos = np.zeros((1, t), np.int32)
        slots = np.full((1, t), trash, np.int32)
        qlens = np.zeros((s,), np.int32)
        ctx = np.zeros((s,), np.int32)
        pt = np.zeros((s, np_b), np.int32)
        src = np.full((1, t), -1, np.int32)
        qsl = np.full((s + 1,), ms, np.int32)
        qsl[:ms + 1] = np.arange(ms + 1)
        qlens[:ms] = 1  # every decode row is a 1-token segment (dead rows
        #                 are masked by ctx == 0, not by qlen)
        rows: list[tuple[Request, int, int]] = []
        spec_map: dict[int, int] = {}
        for i, r in enumerate(plain):
            if prev_rows and r.req_id in prev_rows:
                # input token still in flight: read it device-side from
                # the previous launch's output (host copy is the PENDING
                # placeholder)
                src[0, i] = prev_rows[r.req_id]
            else:
                assert not r._placeholder, "decode input still in flight"
                tokens[0, i] = r.output[-1] if r.output else r.prompt[-1]
            p = r.total_len - 1
            pos[0, i] = p
            ctx[i] = r.total_len
            row = self.page_table[r.slot]
            pt[i] = row[:np_b]
            slots[0, i] = self._page_slots(row, np.asarray(p))
            rows.append((r, i, r._spec_epoch))
            r.context_len = r.total_len
        cur = ms
        for j, r in enumerate(prefill_reqs + spec_reqs):
            i = ms + j
            if r.spec_tokens:
                # speculative verify row: q = drafts+1 resumed chunk
                # feeding [t_{n-1}, d_1..d_k] at positions n-1..n+k-1
                # (n = total_len).  ctx = n+k so each draft attends its
                # predecessors; the fused verify epilogue accepts the
                # longest prefix of drafts matching the sampled targets.
                drafts = r.spec_tokens
                r.spec_tokens = []  # consumed by this launch
                n = len(drafts) + 1
                last = r.output[-1] if r.output else r.prompt[-1]
                tokens[0, cur: cur + n] = [last] + drafts
                p = np.arange(r.total_len - 1, r.total_len - 1 + n,
                              dtype=np.int32)
                ctx[i] = r.total_len - 1 + n
                spec_map[i] = n - 1
                rows.append((r, i, r._spec_epoch))
                r.context_len = r.total_len  # minimum; consume finalizes
            else:
                n = r.num_scheduled_tokens
                chunk = r.prompt[r.chunk_start: r.chunk_start + n]
                tokens[0, cur: cur + n] = chunk
                p = np.arange(r.chunk_start, r.chunk_start + n,
                              dtype=np.int32)
                ctx[i] = r.chunk_start + n
                if r.chunk_start + n == r.num_prompt_tokens:
                    rows.append((r, i, r._spec_epoch))  # completing: samples
                r.context_len = r.chunk_start + n
            pos[0, cur: cur + n] = p  # packed-position RoPE: absolute
            qlens[i] = n
            row = self.page_table[r.slot]
            pt[i] = row[:np_b]
            slots[0, cur: cur + n] = self._page_slots(row, p)
            cur += n
            qsl[i + 1:] = cur

        kcfg = self._dispatch("unified", profile)
        pack = _PackedLaunch(rows=rows, prefill_reqs=list(prefill_reqs),
                             profile=profile, kcfg=kcfg, tokens=t,
                             spec=spec_map)
        batch = {
            "inputs": jnp.asarray(tokens),
            "positions": self._positions(pos),
            "page_table": jnp.asarray(pt),
            "context_lens": jnp.asarray(ctx),
            "query_lens": jnp.asarray(qlens),
            "query_start_loc": jnp.asarray(qsl),
            "slot_mapping": jnp.asarray(slots),
        }
        fill = [(i, r) for r, i, _ in rows]
        pack.sampling = self._sampling_rows(s, fill)
        if self._fused:
            temps, topp, topk, streams, ngen = pack.sampling
            batch["temperature"] = jnp.asarray(temps)
            batch["top_p"] = jnp.asarray(topp)
            batch["top_k"] = jnp.asarray(topk)
            batch["stream_ids"] = jnp.asarray(streams)
            batch["num_generated"] = jnp.asarray(ngen)
            batch["token_source"] = jnp.asarray(src)
            batch["prev_tokens"] = (prev_out if prev_out is not None
                                    else jnp.zeros((s,), jnp.int32))
            if self._spec:
                spec_lens = np.zeros((s,), np.int32)
                for i, k in spec_map.items():
                    spec_lens[i] = k
                batch["spec_lens"] = jnp.asarray(spec_lens)
        return batch, pack

    def _unified_key(self, pack: _PackedLaunch) -> tuple:
        return ("unified", 2 * self.max_seqs, pack.tokens, pack.kcfg)

    def _launch_unified(self, batch: dict, pack: _PackedLaunch, step: int):
        """Dispatch one packed launch of step `step`; returns the
        device-side result ([S] sampled ids fused, [S, V] last logits
        otherwise) WITHOUT transferring it to the host.  The launch
        consumes `self.cache` and leaves the new one there."""
        tel = self.telemetry
        pre_captures = len(self.compile_events)
        exe_key = self._unified_key(pack)
        fn = self._get_fn(*exe_key)
        self.device_calls["unified"] += 1
        cache_in = self.cache
        if exe_key not in self.executables:
            # the launch below reuses this compile
            with self._span("compile", step):
                t_compile = time.perf_counter()
                exe = fn.lower(self.params, cache_in, batch).compile()
                self.executables[exe_key] = exe
                self.compile_seconds += time.perf_counter() - t_compile
                ma = exe.memory_analysis()
                self.cache_alias_bytes[exe_key] = (
                    None if ma is None else ma.alias_size_in_bytes)
        if self._debug_logits:
            self.last_launch = (jax.tree.map(jnp.copy, cache_in), batch)
        with self._span("dispatch", step) as launch:
            ret = fn(self.params, cache_in, batch)
        if self._spec:
            # verify contract: tokens [S, K+1] + num_emitted [S]
            if self._debug_logits:
                toks_d, emitted_d, self.last_step_logits, new_cache = ret
            else:
                toks_d, emitted_d, new_cache = ret
            out = (toks_d, emitted_d)
        elif self._fused and self._debug_logits:
            out, self.last_step_logits, new_cache = ret
        else:
            out, new_cache = ret
        del cache_in  # consumed by the launch
        if tel:
            compiled = len(self.compile_events) > pre_captures
            timed = compiled or tel.time_this_launch()
            if timed:
                jax.block_until_ready(out)
            tel.record_launch(
                "unified", pack.profile, pack.kcfg, launch.t0,
                tel.clock.now(), compiled=compiled, tokens=pack.tokens,
                grid_phase="unified", timed=timed,
                cost=self._launch_costs.get(exe_key))
            if compiled:  # AFTER record_launch: never pollutes timing
                self._exe_cost(exe_key, fn)
        self.cache = new_cache
        self.launched_token_slots += pack.tokens
        return out

    def _consume_unified(self, pack: _PackedLaunch, toks) -> None:
        """Fold one launch's sampled tokens back into request state.
        Rows whose request finished or was preempted while the launch was
        in flight (async loop) are discarded by state / epoch.

        Speculative launches deliver ([S, K+1] tokens, [S] num_emitted):
        a spec row emits its accepted drafts plus the bonus/correction
        token (host-side truncation stops at EOS / max_new_tokens), then
        ROLLS BACK exactly — context_len is finalized to cover only the
        kept tokens (KV past it is never read and is rewritten by later
        steps), and the trailing pages speculation grew are freed through
        the ref-counted allocator.  Those pages are always this step's
        fresh refcount-1 allocations (speculation grows past the
        already-covered total_len), so rollback can never free a shared
        or cached page."""
        tel = self.telemetry
        emitted = None
        if self._spec:
            toks, emitted = toks
        step_proposed = step_accepted = step_emitted = 0
        spec_rows = 0
        for r, row, epoch in pack.rows:
            if r.state is State.FINISHED or r._spec_epoch != epoch:
                continue
            if not self._spec:
                self._emit_token(r, int(toks[row]))
                if tel:
                    tel.requests.token(r)
                continue
            k = pack.spec.get(row, 0)
            e = min(int(emitted[row]), k + 1)
            kept = 0
            for j in range(e):
                self._emit_token(r, int(toks[row, j]))
                kept += 1
                if tel:
                    tel.requests.token(r)
                if r.done:
                    break
            if k:
                # exact rollback: KV is valid through the accepted tokens
                # only (the bonus token's KV is written next step, exactly
                # like a plain decode), and the draft pages beyond the new
                # total_len go back to the pool
                r.context_len = r.total_len - 1
                target = self.alloc.pages_needed(r.total_len)
                if len(r.pages) > target:
                    self.alloc.free(r.pages[target:])
                    del r.pages[target:]
                spec_rows += 1
                step_proposed += k
                step_accepted += kept - 1
                step_emitted += kept
                if self.drafter is not None:
                    self.drafter.observe(k, kept - 1)
        if self._spec:
            self._step_spec = (step_proposed, step_accepted, step_emitted)
            self.spec_stats["proposed"] += step_proposed
            self.spec_stats["accepted"] += step_accepted
            self.spec_stats["emitted"] += step_emitted
            if spec_rows:
                self.spec_stats["steps"] += 1
        if tel:
            for r in pack.prefill_reqs:
                if r.state in (State.PREFILLING, State.RUNNING):
                    tel.requests.chunk(r)

    def _run_prefill(self, reqs: list[Request]) -> None:
        """Execute one scheduled chunk per request.  Chunks starting at
        context 0 (a whole fresh prompt, or the first chunk of a chunked
        one) run the uniform prefill executable; every chunk starting at
        context > 0 — whether the context came from earlier chunks or from
        a prefix-cache hit — runs the cached-context resume executable.
        Only a chunk that completes its prompt samples a token."""
        fresh = [r for r in reqs if r.chunk_start == 0]
        resumed = [r for r in reqs if r.chunk_start > 0]
        if fresh:
            self._run_prefill_fresh(fresh)
        if resumed:
            self._run_prefill_resumed(resumed)

    def _finish_chunk(self, reqs: list[Request], logits) -> None:
        """Advance progress; sample first tokens for prompts now complete."""
        tel = self.telemetry
        done = [(i, r) for i, r in enumerate(reqs)
                if r.chunk_start + r.num_scheduled_tokens
                == r.num_prompt_tokens]
        if done:
            with self._span("wait_tokens", self.step_idx, phase="sample"):
                temps, topp, topk, streams, ngen = self._sampling_rows(
                    logits.shape[0], done)
                self.device_calls["sample"] += 1
                toks = np.asarray(self._sample_fn(
                    logits, jnp.asarray(temps), jnp.asarray(topp),
                    jnp.asarray(topk), jnp.asarray(streams),
                    jnp.asarray(ngen)))
        with self._span("consume", self.step_idx):
            for i, r in done:
                self._emit_token(r, int(toks[i]))
            for r in reqs:
                r.context_len = r.chunk_start + r.num_scheduled_tokens
            if tel:
                done_set = {r.req_id for _, r in done}
                for r in reqs:
                    tel.requests.chunk(r)
                    if r.req_id in done_set:
                        tel.requests.token(r)

    def _launch_padded(self, key: tuple, profile, cache_in, batch: dict,
                       tokens: int, grid_phase: str | None = None):
        """Dispatch one padded-path launch of the executable `key` =
        (kind, batch bucket, seq bucket, KernelConfig) and, with
        telemetry, time it; returns (logits, new_cache)."""
        tel = self.telemetry
        kind = key[0].split("/")[0]
        pre_captures = len(self.compile_events)
        fn = self._get_fn(*key)
        self.device_calls[kind] += 1
        with self._span("dispatch", self.step_idx) as launch:
            logits, new_cache = fn(self.params, cache_in, batch)
        del cache_in  # consumed by the launch
        if tel:
            compiled = len(self.compile_events) > pre_captures
            timed = compiled or tel.time_this_launch()
            if timed:
                jax.block_until_ready(logits)
            tel.record_launch(
                kind, profile, key[3], launch.t0, tel.clock.now(),
                compiled=compiled, tokens=tokens, grid_phase=grid_phase,
                timed=timed, cost=self._launch_costs.get(key))
            if compiled:  # new_cache has the consumed cache's shapes
                self._exe_cost(key, fn, self.params, new_cache, batch)
        self.launched_token_slots += tokens
        return logits, new_cache

    def _run_prefill_fresh(self, reqs: list[Request]) -> None:
        with self._span("pack", self.step_idx) as sp:
            b = next_power_of_2(len(reqs))
            max_len = max(r.num_scheduled_tokens for r in reqs)
            s = max(next_power_of_2(max_len), self.cfg.page_size)
            sp.args["tokens"] = b * s
            tokens = np.zeros((b, s), np.int32)
            qlens = np.zeros((b,), np.int32)
            pt = np.zeros((b, self.pages_per_seq), np.int32)
            pos = np.tile(np.arange(s, dtype=np.int32)[None], (b, 1))
            for i, r in enumerate(reqs):
                n = r.num_scheduled_tokens
                tokens[i, :n] = r.prompt[:n]
                qlens[i] = n
                pt[i] = self.page_table[r.slot]

            cache_in = self._prefill_cache_view(b)
            profile = self._prefill_profile(reqs)
            kcfg = self._dispatch("prefill", profile)
            batch = {
                "inputs": jnp.asarray(tokens),
                "positions": self._positions(pos),
                "page_table": jnp.asarray(pt),
                "context_lens": jnp.asarray(qlens),
                "query_lens": jnp.asarray(qlens),
            }
        logits, new_cache = self._launch_padded(
            ("prefill", b, s, kcfg), profile, cache_in, batch, b * s,
            grid_phase="prefill")
        self._merge_prefill_cache(new_cache, [r.slot for r in reqs])
        self._finish_chunk(reqs, logits)

    def _run_prefill_resumed(self, reqs: list[Request]) -> None:
        """Resumable prefill (context > 0): embed/compute only this step's
        chunk; attention reads the prior context — earlier chunks and/or a
        shared cached prefix — back from the pages
        (context_lens = chunk_start + chunk)."""
        with self._span("pack", self.step_idx) as sp:
            b = next_power_of_2(len(reqs))
            max_chunk = max(r.num_scheduled_tokens for r in reqs)
            s = max(next_power_of_2(max_chunk), self.cfg.page_size)
            sp.args["tokens"] = b * s
            # page-table width bucket: attend only over the pages the
            # longest context actually uses, not the full max_model_len
            # table (the xla path gathers the whole table width)
            max_ctx = max(r.chunk_start + r.num_scheduled_tokens
                          for r in reqs)
            np_b = min(self.pages_per_seq,
                       next_power_of_2(cdiv(max_ctx, self.cfg.page_size)))
            tokens = np.zeros((b, s), np.int32)
            qlens = np.zeros((b,), np.int32)
            ctx = np.zeros((b,), np.int32)
            pt = np.zeros((b, np_b), np.int32)
            pos = np.tile(np.arange(s, dtype=np.int32)[None], (b, 1))
            for i, r in enumerate(reqs):
                chunk = r.prompt[r.chunk_start:
                                 r.chunk_start + r.num_scheduled_tokens]
                tokens[i, : len(chunk)] = chunk
                qlens[i] = len(chunk)
                ctx[i] = r.chunk_start + r.num_scheduled_tokens
                pos[i] += r.chunk_start  # absolute positions
                pt[i] = self.page_table[r.slot][:np_b]

            cache_in = self._prefill_cache_view(b)
            profile = self._prefill_profile(reqs)
            kcfg = self._dispatch("prefill_cached", profile)
            batch = {
                "inputs": jnp.asarray(tokens),
                "positions": self._positions(pos),
                "page_table": jnp.asarray(pt),
                "context_lens": jnp.asarray(ctx),
                "query_lens": jnp.asarray(qlens),
            }
        logits, new_cache = self._launch_padded(
            (f"prefill_cached/np{np_b}", b, s, kcfg), profile, cache_in,
            batch, b * s, grid_phase="prefill")
        self._merge_prefill_cache(new_cache, [r.slot for r in reqs])
        self._finish_chunk(reqs, logits)

    def _run_decode(self, reqs: list[Request]) -> None:
        tel = self.telemetry
        b = self.max_seqs  # static decode batch (paper C5)
        with self._span("pack", self.step_idx, tokens=b):
            tokens = np.zeros((b, 1), np.int32)
            pos = np.full((b, 1), -1, np.int32)
            ctx = np.zeros((b,), np.int32)
            for r in reqs:
                tokens[r.slot, 0] = r.output[-1] if r.output \
                    else r.prompt[-1]
                pos[r.slot, 0] = r.total_len - 1
                ctx[r.slot] = r.total_len
            temps, topp, topk, streams, ngen = self._sampling_rows(
                b, [(r.slot, r) for r in reqs])
            profile = self._decode_profile(reqs)
            kcfg = self._dispatch("decode", profile)
            cache_in = self.cache
            batch = {
                "inputs": jnp.asarray(tokens),
                "positions": self._positions(pos),
                "page_table": jnp.asarray(self.page_table),
                "context_lens": jnp.asarray(ctx),
            }
        logits, self.cache = self._launch_padded(
            ("decode", b, 1, kcfg), profile, cache_in, batch, b)
        with self._span("wait_tokens", self.step_idx, phase="sample"):
            self.device_calls["sample"] += 1
            toks = np.asarray(self._sample_fn(
                logits, jnp.asarray(temps), jnp.asarray(topp),
                jnp.asarray(topk), jnp.asarray(streams),
                jnp.asarray(ngen)))
        with self._span("consume", self.step_idx):
            for r in reqs:
                r.context_len = r.total_len
                self._emit_token(r, int(toks[r.slot]))
                if tel:
                    tel.requests.token(r)

    # ------------------------------------------------------------------
    # slot-indexed (SSM) cache plumbing
    # ------------------------------------------------------------------

    def _prefill_cache_view(self, b: int):
        """Attn pages are global (so chunk-resume reads earlier chunks /
        cached prefixes straight from them); SSM rows start from zeros —
        SSM-family prefill always begins at context 0 (chunked prefill and
        prefix caching are gated to attention families)."""
        view = {}
        for k, v in self.cache.items():
            if k == "attn":
                view[k] = v
            else:
                zeros = jax.tree.map(
                    lambda t: jnp.zeros(t.shape[:1] + (b,) + t.shape[2:],
                                        t.dtype), v)
                if k in ("mlstm", "slstm"):
                    zeros["m"] = jnp.full_like(zeros["m"], -jnp.inf)
                view[k] = zeros
        return view

    def _merge_prefill_cache(self, new_cache, slots: list[int]) -> None:
        idx = jnp.asarray(slots, jnp.int32)
        merged = {}
        for k, v in new_cache.items():
            if k == "attn":
                merged[k] = v
            else:
                merged[k] = jax.tree.map(
                    lambda full, new: full.at[:, idx].set(
                        new[:, : len(slots)]),
                    self.cache[k], v,
                )
        for k in self.cache:
            if k not in merged:
                merged[k] = self.cache[k]
        self.cache = merged
