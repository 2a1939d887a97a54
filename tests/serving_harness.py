"""Shared serving-test harness.

One place for the engine-test plumbing every serving suite used to
copy-paste: model/param construction, engine building, stepping a request
set to completion, and DIFFERENTIAL comparison of two runs.

The core idea is that most serving features (chunked prefill, prefix
caching, preemption) are scheduling/memory-management changes whose only
acceptable observable effect is WHEN tokens are computed — never WHAT is
computed.  `run_requests` therefore checks per-step invariants (token
budget, allocator page conservation) while it drives the engine, and
`assert_same_outputs` asserts token-for-token equality between engine
configurations; `greedy_reference` pins both to the dense cacheless
forward as ground truth.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, reduced
from repro.models import model as M
from repro.serving.engine import Engine
from repro.serving.request import Request, State, make_requests

# ---------------------------------------------------------------------------
# construction
# ---------------------------------------------------------------------------


def build_cfg_params(arch: str = "smollm-135m", seed: int = 0, **overrides):
    """(cfg, params) of the reduced test model — wrap in a module-scoped
    fixture so each test module pays init once.  `overrides` patch cfg
    fields on top of the reduction (the mesh suites need head counts
    divisible by tp; the reduced default is 2 q / 1 kv head)."""
    cfg = reduced(ARCHS[arch]).replace(dtype="float32", **overrides)
    params = M.init(cfg, jax.random.key(seed))
    return cfg, params


def build_engine(cfg, params, *, max_seqs: int = 4, num_pages: int = 64,
                 max_model_len: int = 256, **kw) -> Engine:
    return Engine(cfg, params, max_seqs=max_seqs, num_pages=num_pages,
                  max_model_len=max_model_len, **kw)


def make_prompts(cfg, rng, lens):
    return [list(rng.integers(1, cfg.vocab_size, size=int(n)))
            for n in lens]


def shared_prefix_prompts(cfg, rng, prefix_len, tails):
    shared = list(rng.integers(1, cfg.vocab_size, size=prefix_len))
    return [shared + list(rng.integers(1, cfg.vocab_size, size=int(n)))
            for n in tails]


# ---------------------------------------------------------------------------
# run-to-completion with per-step invariants
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RunResult:
    engine: Engine
    requests: list[Request]
    step_stats: list[dict]

    @property
    def outputs(self) -> list[list[int]]:
        return [r.output for r in self.requests]

    @property
    def num_steps(self) -> int:
        return len(self.step_stats)

    @property
    def last_stats(self) -> dict:
        return self.step_stats[-1]

    def total(self, key: str) -> int:
        return sum(s[key] for s in self.step_stats)


def assert_step_invariants(eng: Engine, stats: dict) -> None:
    """Per-step serving invariants.

    Budget: scheduled prefill tokens never exceed the per-step prefill
    budget; with chunked prefill the budget is TOTAL — each scheduled
    decode charges one token, partial prefills fill the remainder (decodes
    are never displaced, so a decode-saturated step may legitimately hold
    `decode > budget` with zero prefill tokens).

    Page conservation: the running requests' page lists account for every
    page reference (shared pages appear once per holder), and referenced /
    evictable / free pages partition the pool — `check_invariants` makes
    leaks and double-books hard errors mid-run, not just at drain time.
    """
    sched = eng.sched
    assert stats["prefill_tokens"] <= sched.max_prefill_tokens, stats
    if sched.enable_chunked_prefill:
        assert (stats["prefill_tokens"] + stats["decode"]
                <= max(sched.max_prefill_tokens, stats["decode"])), stats
    eng.alloc.check_invariants([r.pages for r in sched.running])
    # the allocator snapshot surfaced in step stats must agree with the
    # pool it describes — per device AND in aggregate.  `pool` is the
    # mesh aggregate (every stat summed over per-device views; under
    # head-sharded tp each device mirrors the page occupancy, so the
    # aggregate is num_devices x the host pool), and each per-device
    # view must itself partition [1, num_pages) and account for every
    # running request's page references.
    pool = stats["pool"]
    n_dev = pool.get("num_devices", 1)
    assert n_dev == getattr(eng, "tp", 1), pool
    refs = sum(len(r.pages) for r in sched.running)
    assert (pool["free_pages"] + pool["referenced_pages"]
            + pool["evictable_pages"]
            == n_dev * (eng.alloc.num_pages - 1)), pool
    assert pool["total_refs"] == n_dev * refs, pool
    for dev in pool.get("per_device", [pool]):
        assert (dev["free_pages"] + dev["referenced_pages"]
                + dev["evictable_pages"] == eng.alloc.num_pages - 1), dev
        assert dev["total_refs"] == refs, dev
    # a packed launch donates the cache: its executable aliases the whole
    # KV pool (per device) to its output, so the pool is updated in place
    if "cache_alias_bytes" in stats:
        assert stats["kv_pool_bytes"] == eng.kv_pool_bytes > 0, stats
        assert stats["cache_alias_bytes"] >= eng.kv_pool_bytes, stats
    # the sampler counts rows of this launch: a search's rows draw, and
    # only scheduled requests draw
    if "sampler" in stats:
        smp = stats["sampler"]
        assert max(smp["top_p_rows"], smp["top_k_rows"]) \
            <= smp["sampled_rows"] <= stats["decode"] + stats["prefill"], smp


def model_launches(eng: Engine) -> int:
    return sum(n for k, n in eng.device_calls.items() if k != "sample")


def pool_leaves(eng: Engine) -> list:
    """The arrays of the engine's KV pool (none for a pure SSM model)."""
    return jax.tree.leaves(eng.cache.get("attn"))


def assert_cache_consumed(eng: Engine, before: list, launches: int) -> None:
    """A step that launched the model consumed the KV pool it was given
    (`before`, from `pool_leaves`): every array of it is deleted, and
    `eng.cache` holds live arrays.  Slot-indexed SSM state is not a pool:
    a padded prefill starts it from zeros and merges rows back."""
    if model_launches(eng) == launches:
        return
    assert all(a.is_deleted() for a in before), "launch input pool alive"
    assert not any(a.is_deleted() for a in jax.tree.leaves(eng.cache))


def run_requests(eng: Engine, prompts, *, max_new_tokens: int = 8,
                 max_steps: int = 10_000, check_invariants: bool = True,
                 expect_finished: bool = True, **req_kw) -> RunResult:
    """Submit one request per prompt and step the engine until it drains,
    checking per-step invariants along the way."""
    reqs = make_requests([list(p) for p in prompts],
                         max_new_tokens=max_new_tokens, **req_kw)
    for r in reqs:
        eng.add_request(r)
    stats: list[dict] = []
    while eng.sched.has_work and len(stats) < max_steps:
        before, launches = pool_leaves(eng), model_launches(eng)
        st = eng.step()
        stats.append(st)
        if check_invariants:
            assert_step_invariants(eng, st)
            assert_cache_consumed(eng, before, launches)
    assert not eng.sched.has_work, \
        f"engine did not drain within {max_steps} steps"
    if expect_finished:
        assert all(r.state is State.FINISHED for r in reqs), \
            [r.state for r in reqs]
        assert eng.alloc.free_pages == eng.num_pages - 1, "pages leaked"
    return RunResult(eng, reqs, stats)


# ---------------------------------------------------------------------------
# telemetry cross-check
# ---------------------------------------------------------------------------


def assert_telemetry_consistent(res: RunResult) -> None:
    """The telemetry subsystem must agree with the engine's own ground
    truth: every counter it accumulated over a drained run is re-derivable
    from engine state and the per-step stats the harness collected."""
    eng = res.engine
    tel = eng.telemetry
    assert tel is not None, "run the engine with telemetry=Telemetry()"
    m = tel.metrics

    assert m.value("repro_steps_total") == res.num_steps
    assert (m.value("repro_launched_token_slots_total")
            == eng.launched_token_slots)
    # num_generated counts every token a request kept, those that a
    # preemption folded into its prompt too (len(output) elsewhere)
    assert (m.value("repro_tokens_total", kind="sampled")
            == sum(r.num_generated for r in res.requests))
    assert (m.value("repro_tokens_total", kind="prefill")
            == res.total("prefill_tokens"))
    assert (m.value("repro_tokens_total", kind="cached_prefill")
            == res.total("cached_tokens") == eng.cached_prefill_tokens)
    assert (m.value("repro_scheduler_events_total", event="preempted")
            == res.total("preempted"))

    # one capture counter tick per engine compile event, one dispatch
    # counter tick per engine dispatch decision
    snap = m.snapshot()
    compiles = sum(s["value"] for s
                   in snap["repro_compile_events_total"]["series"])
    assert compiles == len(eng.compile_events)
    for (phase, variant), n in eng.dispatch_counts.items():
        assert m.value("repro_dispatch_total",
                       phase=phase, variant=variant) == n

    # request lifecycle records: every submitted request tracked, token
    # counts exact per request
    recs = tel.requests.records
    assert len(recs) == len(res.requests)
    for r in res.requests:
        rec = recs[r.req_id]
        folded = r.num_generated - len(r.output)
        assert rec.num_tokens == r.num_generated, (rec, r.output)
        assert rec.prompt_tokens == r.num_prompt_tokens - folded
        if r.output:
            assert rec.first_token_t is not None
            assert rec.ttft is not None and rec.ttft >= 0.0
        # queue wait and preemptions are the request's own stamps, in
        # lifecycle order: submitted <= admitted <= first token
        assert (rec.submit_t, rec.admit_t, rec.queue_time,
                rec.preemptions) == (r.submitted_at, r.admitted_at,
                                     r.queue_wait, r.preemptions)
        if r.output:
            assert (r.submitted_at <= r.admitted_at <= rec.first_token_t
                    <= rec.finish_t)
            assert r.queue_wait >= r.admitted_at - r.submitted_at
    assert (sum(r.preemptions for r in res.requests)
            == eng.sched.preemptions == res.total("preempted"))

    # pool gauges reflect the allocator at the last step
    pool = eng.alloc.stats()
    for state in ("free", "referenced", "evictable", "shared", "cached"):
        assert (m.value("repro_pool_pages", state=state)
                == pool[f"{state}_pages"]), state
    assert m.value("repro_pool_page_refs") == pool["total_refs"]

    # padding accounting: waste ratio is a true fraction of launched slots
    waste = m.value("repro_padding_waste_ratio")
    assert 0.0 <= waste < 1.0, waste

    # the trace buffer must hold a loadable Chrome trace: step spans plus
    # one lifetime span per finished request
    doc = tel.tracer.to_json()
    names = [ev["name"] for ev in doc["traceEvents"]]
    assert names.count("step") == res.num_steps
    for r in res.requests:
        if r.done:
            assert f"request {r.req_id}" in names
    for ev in doc["traceEvents"]:
        assert {"name", "ph", "pid", "tid"} <= set(ev), ev
        if ev["ph"] == "X":
            assert ev["ts"] >= 0.0 and ev["dur"] >= 0.0, ev


# ---------------------------------------------------------------------------
# differential comparison
# ---------------------------------------------------------------------------


def assert_same_outputs(a: RunResult, b: RunResult, *,
                        label_a: str = "a", label_b: str = "b") -> None:
    """Token-for-token equality of two runs over the same request set."""
    assert len(a.requests) == len(b.requests)
    for i, (ra, rb) in enumerate(zip(a.requests, b.requests)):
        assert ra.output == rb.output, (
            f"request {i} (prompt len {ra.num_prompt_tokens}): outputs "
            f"diverge between {label_a} and {label_b}\n"
            f"  {label_a}: {ra.output}\n  {label_b}: {rb.output}")


def greedy_reference(cfg, params, prompt, num_tokens: int) -> list[int]:
    """Dense (cacheless) greedy continuation — the ground truth every
    engine configuration must reproduce exactly."""
    toks = list(prompt)
    for _ in range(num_tokens):
        x = jnp.asarray(toks)[None]
        logits, _, _ = M.forward(
            cfg, params, x, M.default_positions(cfg, 1, len(toks)),
            mode="train",
        )
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]
