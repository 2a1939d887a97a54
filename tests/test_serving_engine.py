"""Serving engine: continuous batching, paging, preemption, exactness,
and profile-driven kernel-config dispatch.

Engine plumbing (build/run/compare) lives in serving_harness.py — shared
with test_prefix_cache.py and test_chunked_prefill.py.
"""
import json
import os
import tempfile

import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # collect-and-skip fallback (requirements-dev.txt)
    from _hypothesis_fallback import given, settings, st

import serving_harness as H
from repro.core.attention import heuristics
from repro.core.paged.allocator import OutOfPages, PageAllocator
from repro.obs import FakeClock, Telemetry
from repro.serving.request import Request


@pytest.fixture(scope="module")
def smollm():
    return H.build_cfg_params()


def test_engine_greedy_matches_dense(smollm):
    cfg, params = smollm
    rng = np.random.default_rng(0)
    prompts = H.make_prompts(cfg, rng, (17, 5))
    run = H.run_requests(H.build_engine(cfg, params), prompts,
                         max_new_tokens=8)
    for p, out in zip(prompts, run.outputs):
        assert out == H.greedy_reference(cfg, params, p, 8)


def test_engine_more_requests_than_slots(smollm):
    cfg, params = smollm
    rng = np.random.default_rng(1)
    run = H.run_requests(
        H.build_engine(cfg, params, max_seqs=2, max_model_len=128),
        H.make_prompts(cfg, rng, (9, 3, 17, 5, 8)), max_new_tokens=4)
    assert all(len(out) == 4 for out in run.outputs)


def test_engine_preemption_under_page_pressure(smollm):
    cfg, params = smollm
    # tiny pool: 2 requests cannot both hold their full length
    rng = np.random.default_rng(2)
    run = H.run_requests(
        H.build_engine(cfg, params, max_seqs=2, num_pages=7,
                       max_model_len=64),
        H.make_prompts(cfg, rng, (30, 30)), max_new_tokens=16)
    assert all(len(out) == 16 for out in run.outputs)


def test_request_stamps_under_preemption(smollm):
    """A pool too small for both requests' full lengths forces
    preemption: every request's stamps are in lifecycle order, each
    preemption is counted once on the request and on the scheduler, a
    preempted request's queue wait includes its wait for re-admission,
    and the telemetry's request records agree with the stamps."""
    cfg, params = smollm
    rng = np.random.default_rng(2)
    res = H.run_requests(
        H.build_engine(cfg, params, max_seqs=2, num_pages=7,
                       max_model_len=128,
                       telemetry=Telemetry(clock=FakeClock(tick=1e-3))),
        H.make_prompts(cfg, rng, (30, 30)), max_new_tokens=20)
    sched = res.engine.sched
    assert sched.preemptions >= 1
    for r in res.requests:
        assert r.submitted_at < r.admitted_at and r.queued_at is None
    preempted = [r for r in res.requests if r.preemptions]
    assert sum(r.preemptions for r in preempted) == sched.preemptions
    for r in preempted:
        assert r.queue_wait > r.admitted_at - r.submitted_at
    H.assert_telemetry_consistent(res)


def test_engine_static_decode_batch_and_bucketing(smollm):
    """The CUDA-graph-analog: executables are keyed by (kind, batch-bucket,
    seq-bucket, KernelConfig) — decode always uses the static max_seqs
    batch, prefill one (batch, seq) bucket per shape, and the kernel-config
    dispatch adds AT MOST one capture per distinct config (never one per
    step).  This documents the PADDED per-kind path (the packed default's
    bucketing contract lives in test_unified_attention.py)."""
    cfg, params = smollm
    rng = np.random.default_rng(3)
    eng = H.build_engine(cfg, params, packed_attention=False)
    H.run_requests(eng, H.make_prompts(cfg, rng, (5, 9, 17, 33, 12, 7)),
                   max_new_tokens=4)
    decode_events = [e for e in eng.compile_events if e[0] == "decode"]
    # static decode batch: every decode capture is (max_seqs, 1); the tree
    # may pick a handful of distinct configs, each captured exactly once
    assert all(e[1:3] == (4, 1) for e in decode_events)
    assert len(decode_events) == len({e[3] for e in decode_events})
    assert len(decode_events) <= 3  # bounded by configs, not steps
    for kind, b, s, kcfg in eng.compile_events:
        assert b & (b - 1) == 0  # power-of-two buckets
        assert s & (s - 1) == 0 or s == 1


def _install_tree(tmpdir: str) -> str:
    """A synthetic tuned tree with the paper's §4.5 shape: segmented for
    small-batch long-context decode, gqa otherwise."""
    seg = {"variant": "segmented", "tile": None, "num_segments": 4,
           "block_q": 16}
    gqa = {"variant": "gqa", "tile": None, "num_segments": 8, "block_q": 16}
    path = os.path.join(tmpdir, "tree.json")
    with open(path, "w") as f:
        json.dump({
            "decode_tree": [
                [{"num_seqs_le": 1, "max_context_ge": 64}, seg],
                [{}, gqa],
            ],
            "prefill_tree": [[{}, gqa]],
        }, f)
    return path


def test_engine_dispatch_switches_variant_by_batch_shape(smollm):
    """With a tuned tree installed the engine demonstrably switches kernel
    variants by batch shape: a lone long-context request decodes through
    `segmented`, a 4-wide short-context batch through `gqa` — and every
    step's choice surfaces in the stats.  (Padded path: the decode tree
    only steers per-kind launches; the packed analog is
    test_packed_dispatch_uses_unified_tree.)"""
    cfg, params = smollm
    rng = np.random.default_rng(5)
    with tempfile.TemporaryDirectory() as d:
        heuristics.load(_install_tree(d))
        try:
            # 4 short requests: num_seqs > 1 -> gqa leaf
            wide = H.run_requests(
                H.build_engine(cfg, params, packed_attention=False),
                H.make_prompts(cfg, rng, (8, 11, 5, 9)), max_new_tokens=4)
            assert wide.engine.dispatch_counts[("decode", "gqa")] > 0
            assert wide.engine.dispatch_counts[("decode", "segmented")] == 0
            # 1 long request: num_seqs == 1, context >= 64 -> segmented
            deep = H.run_requests(
                H.build_engine(cfg, params, packed_attention=False),
                H.make_prompts(cfg, rng, (60,)), max_new_tokens=8)
            assert deep.engine.dispatch_counts[("decode", "segmented")] > 0
            disp = [st["dispatch"]["decode"] for st in deep.step_stats
                    if "decode" in st["dispatch"]]
            assert all(dd["variant"] == "segmented" and
                       dd["num_segments"] == 4 for dd in disp)
        finally:
            heuristics.reset()


def test_engine_auto_budget_never_blocks_unchunked_admission(smollm):
    """max_prefill_tokens='auto' resolves the roofline chunk budget — but
    without chunked prefill the budget gates MONOLITHIC admission, so it
    must be clamped up to max_model_len or a prompt longer than the chunk
    suggestion would wait in the queue forever."""
    cfg, params = smollm
    rng = np.random.default_rng(7)
    tree = {"decode_tree": [], "prefill_tree": [],
            "suggested_max_prefill_tokens": 32}
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "tree.json")
        with open(path, "w") as f:
            json.dump(tree, f)
        heuristics.load(path)
        try:
            eng = H.build_engine(cfg, params, max_model_len=256,
                                 max_prefill_tokens="auto")
            assert eng.sched.max_prefill_tokens >= 256  # clamped
            run = H.run_requests(eng, H.make_prompts(cfg, rng, (200,)),
                                 max_new_tokens=2, max_steps=50)
            assert len(run.outputs[0]) == 2
            # chunked engines keep the tuned chunk budget as-is
            eng2 = H.build_engine(cfg, params, max_model_len=256,
                                  max_prefill_tokens="auto",
                                  enable_chunked_prefill=True)
            assert eng2.sched.max_prefill_tokens == 32
        finally:
            heuristics.reset()


def test_engine_per_config_executable_caching(smollm):
    """Per-(bucket x KernelConfig) executable reuse: recurring configs
    replay the captured graph — re-serving an identical workload adds ZERO
    captures, every capture key is unique, and a variant flip mid-serve
    costs exactly one capture for the new config.  (Padded path; the
    packed equivalent is covered in test_unified_attention.py.)"""
    cfg, params = smollm
    rng = np.random.default_rng(6)
    prompts = H.make_prompts(cfg, rng, (9, 14))
    with tempfile.TemporaryDirectory() as d:
        heuristics.load(_install_tree(d))
        try:
            eng = H.build_engine(cfg, params, max_seqs=2,
                                 packed_attention=False)

            def serve():
                # the short request drains first; the survivor decodes
                # alone (num_seqs==1) past the context-64 bucket, so the
                # tree flips gqa -> segmented mid-serve
                reqs = [Request(prompt=list(prompts[0]), max_new_tokens=8),
                        Request(prompt=list(prompts[1]), max_new_tokens=60)]
                for r in reqs:
                    eng.add_request(r)
                while eng.sched.has_work:
                    eng.step()

            serve()
            events_first = list(eng.compile_events)
            assert len(events_first) == len(set(events_first))
            variants = {e[3].variant for e in events_first
                        if e[0] == "decode"}
            assert variants == {"gqa", "segmented"}
            assert eng.dispatch_counts[("decode", "segmented")] > 1, \
                "variant recurred but was captured once (see next assert)"
            # identical workload again: every (bucket, config) recurs ->
            # no new captures
            serve()
            assert eng.compile_events == events_first
        finally:
            heuristics.reset()


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["zamba2-1.2b", "xlstm-350m"])
def test_engine_ssm_archs(arch):
    """Hybrid/SSM archs serve through the engine (state caches + pages)."""
    cfg, params = H.build_cfg_params(arch)
    rng = np.random.default_rng(4)
    prompts = H.make_prompts(cfg, rng, (12, 20, 7))
    run = H.run_requests(
        H.build_engine(cfg, params, max_seqs=2, num_pages=32,
                       max_model_len=128),
        prompts, max_new_tokens=4)
    # exactness vs dense forward (recurrent caches must carry across steps)
    for p, out in zip(prompts, run.outputs):
        assert out == H.greedy_reference(cfg, params, p, 4), arch


# ---------------------------------------------------------------------------
# allocator invariants (hypothesis)
# ---------------------------------------------------------------------------


@settings(max_examples=50, deadline=None)
@given(st.data())
def test_allocator_never_double_books(data):
    num_pages = data.draw(st.integers(4, 64))
    alloc = PageAllocator(num_pages, 16)
    held: list[list[int]] = []
    for _ in range(data.draw(st.integers(1, 30))):
        if held and data.draw(st.booleans()):
            alloc.free(held.pop(data.draw(
                st.integers(0, len(held) - 1))))
        else:
            n = data.draw(st.integers(1, 4))
            if alloc.can_allocate(n):
                pages = alloc.allocate(n)
                assert 0 not in pages  # NULL page never handed out
                held.append(pages)
            else:
                with pytest.raises(OutOfPages):
                    alloc.allocate(n)
        alloc.check_invariants(held)


def test_scheduler_conserves_tokens(smollm):
    """Preempted-and-resumed requests still produce the same greedy text."""
    cfg, params = smollm
    rng = np.random.default_rng(5)
    prompts = H.make_prompts(cfg, rng, (24, 24))
    runs = [
        H.run_requests(
            H.build_engine(cfg, params, max_seqs=2, num_pages=num_pages,
                           max_model_len=64),
            prompts, max_new_tokens=8)
        for num_pages in (64, 7)  # ample vs starved (forces preemption)
    ]
    H.assert_same_outputs(runs[0], runs[1], label_a="ample",
                          label_b="starved")
