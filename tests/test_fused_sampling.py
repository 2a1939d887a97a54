"""Fused packed sampling + async double-buffered loop (docs/serving.md).

Three claim families:

1. **Launch contract** — a steady-state packed step with fused sampling
   is exactly ONE device dispatch (`Engine.device_calls`), and greedy
   outputs are token-for-token identical to the retained two-dispatch
   packed baseline, the padded path, and the dense cacheless reference.

2. **Sampling correctness** — the greedy temperature divisor is clamped
   (no 1e6 blow-up on large logits); top-k / top-p filters match a numpy
   reference; and RNG is a pure function of (engine seed, stream id,
   tokens generated): seeded sampled outputs are bit-identical across
   packed/padded engines, batch compositions, and the async loop.

3. **Async loop** — `submit()`/`stream()` yields exactly the tokens the
   requests end with, matches the synchronous engine token-for-token
   (greedy and seeded), survives EOS on a prompt-completing chunk, and
   `generate()` no longer exhausts max_steps silently.
"""
from __future__ import annotations

import logging

import jax
import numpy as np
import pytest

from repro.models import sampling
from serving_harness import (assert_cache_consumed, assert_same_outputs,
                             assert_step_invariants, build_cfg_params,
                             build_engine, greedy_reference, make_prompts,
                             model_launches, pool_leaves, run_requests)
from repro.serving.request import Request, State

MAX_NEW = 6
LENS = [5, 9, 3, 12, 7]


@pytest.fixture(scope="module")
def cfg_params():
    return build_cfg_params()


@pytest.fixture()
def prompts(cfg_params):
    cfg, _ = cfg_params
    return make_prompts(cfg, np.random.default_rng(0), LENS)


def stream_requests(eng, reqs, **kw):
    """Drive `stream()` to drain; returns the yielded (req_id, token)
    pairs grouped per request, checking step invariants as it goes —
    among them that each launch consumed the cache before it (the
    double-buffered loop hands step N's output cache to step N+1)."""
    for r in reqs:
        eng.submit(r)
    by_req: dict[int, list[int]] = {}
    before, launches = pool_leaves(eng), model_launches(eng)
    for rid, tok in eng.stream(**kw):
        by_req.setdefault(rid, []).append(tok)
        assert_step_invariants(eng, eng.last_step_stats)
        assert_cache_consumed(eng, before, launches)
        before, launches = pool_leaves(eng), model_launches(eng)
    return by_req


def sampled_requests(prompts, subset=None, **req_kw):
    reqs = [Request(prompt=list(p), max_new_tokens=MAX_NEW,
                    temperature=0.8, top_p=0.9, top_k=20, seed=1000 + i,
                    **req_kw)
            for i, p in enumerate(prompts)]
    if subset is not None:
        reqs = [reqs[i] for i in subset]
    return reqs


def drain(eng, reqs):
    for r in reqs:
        eng.add_request(r)
    while eng.sched.has_work:
        eng.step()
    assert all(r.state is State.FINISHED for r in reqs)
    return {r.seed: r.output for r in reqs}


# ---------------------------------------------------------------------------
# 1. launch contract
# ---------------------------------------------------------------------------


def test_fused_step_is_one_dispatch(cfg_params, prompts):
    """Steady-state fused packed step = exactly one device dispatch and
    zero new captures; the two-dispatch baseline pays a sample launch."""
    cfg, params = cfg_params
    eng = build_engine(cfg, params)
    res = run_requests(eng, prompts, max_new_tokens=MAX_NEW)
    assert set(eng.device_calls) == {"unified"}
    assert eng.device_calls["unified"] == res.num_steps

    # per-step: a decode-only steady step adds {"unified": 1} and nothing
    # else, with no recompilation
    eng2 = build_engine(cfg, params)
    reqs = [Request(prompt=list(p), max_new_tokens=MAX_NEW)
            for p in prompts]
    for r in reqs:
        eng2.add_request(r)
    eng2.step()  # prefill + capture step
    eng2.step()  # decode warm-up (captures the decode-only bucket)
    before = dict(eng2.device_calls)
    captures = len(eng2.compile_events)
    st = eng2.step()
    assert st["decode"] > 0 and st["prefill"] == 0
    after = dict(eng2.device_calls)
    assert {k: after[k] - before.get(k, 0) for k in after
            if after[k] != before.get(k, 0)} == {"unified": 1}
    assert len(eng2.compile_events) == captures, "steady step recompiled"

    eng3 = build_engine(cfg, params, fused_sampling=False)
    run_requests(eng3, prompts, max_new_tokens=MAX_NEW)
    assert eng3.device_calls["sample"] > 0


def test_greedy_identity_across_paths(cfg_params, prompts):
    """Fused == two-dispatch packed == padded, greedy, and all match the
    dense cacheless reference."""
    cfg, params = cfg_params
    res_f = run_requests(build_engine(cfg, params), prompts,
                         max_new_tokens=MAX_NEW)
    res_2 = run_requests(build_engine(cfg, params, fused_sampling=False),
                         prompts, max_new_tokens=MAX_NEW)
    res_p = run_requests(build_engine(cfg, params, packed_attention=False),
                         prompts, max_new_tokens=MAX_NEW)
    assert_same_outputs(res_f, res_2, label_a="fused", label_b="two-dispatch")
    assert_same_outputs(res_f, res_p, label_a="fused", label_b="padded")
    ref = greedy_reference(cfg, params, prompts[0], MAX_NEW)
    assert res_f.requests[0].output == ref


def test_debug_logits_flag(cfg_params, prompts):
    """`debug_logits=True` exposes the per-seq last-token logits without
    changing sampled tokens."""
    cfg, params = cfg_params
    eng = build_engine(cfg, params, debug_logits=True)
    res = run_requests(eng, prompts[:2], max_new_tokens=3)
    assert eng.last_step_logits is not None
    assert eng.last_step_logits.shape == (2 * eng.max_seqs, cfg.vocab_size)
    ref = run_requests(build_engine(cfg, params), prompts[:2],
                       max_new_tokens=3)
    assert_same_outputs(res, ref, label_a="debug", label_b="production")


# ---------------------------------------------------------------------------
# 2. sampling correctness
# ---------------------------------------------------------------------------


def test_greedy_divisor_clamped():
    """temperature == 0 rows must pass logits through UNCHANGED (divisor
    1.0): the historical max(t, 1e-6) multiplied by 1e6 and overflowed
    large / -inf-masked logits on the dead branch."""
    logits = np.array([[3.0e38, -3.0e38, 1.0],
                       [1.0, 2.0, 3.0]], np.float32)
    temps = np.zeros((2,), np.float32)
    scaled = np.asarray(sampling.scaled_logits(logits, temps))
    np.testing.assert_array_equal(scaled, logits)
    assert np.isfinite(scaled[0, 0]), "greedy row blew up"
    # and the full sampler stays finite/greedy on them
    keys = sampling.request_keys(0, np.arange(2, dtype=np.int32),
                                 np.zeros(2, np.int32))
    toks = np.asarray(sampling.sample_tokens(
        logits, temps, np.ones(2, np.float32), np.zeros(2, np.int32), keys))
    np.testing.assert_array_equal(toks, np.argmax(logits, axis=-1))


def _numpy_filter(logits, temperature, top_p, top_k):
    """Reference kept-token sets: scale -> top-k -> top-p, keeping ties."""
    x = logits.astype(np.float64).copy()
    for i in range(x.shape[0]):
        t = temperature[i] if temperature[i] > 0 else 1.0
        x[i] = x[i] / t
        if top_k[i] > 0:
            kth = np.sort(x[i])[::-1][min(top_k[i], x.shape[1]) - 1]
            x[i][x[i] < kth] = -np.inf
        if top_p[i] < 1.0:
            order = np.argsort(-x[i], kind="stable")
            probs = np.exp(x[i][order] - np.max(x[i][order]))
            probs = probs / probs.sum()
            cum = np.cumsum(probs)
            keep = (cum - probs) < top_p[i]
            thresh = np.min(x[i][order][keep])
            x[i][x[i] < thresh] = -np.inf
    return np.isfinite(x)


def _kept_case(name):
    """(logits [S, V], temperature, top_p, top_k) of one kept-set case."""
    rng = np.random.default_rng(7)
    f32, i32 = np.float32, np.int32
    if name == "small":
        return (rng.normal(size=(6, 32)).astype(f32) * 3,
                np.array([0.0, 0.5, 1.0, 0.7, 1.3, 1.0], f32),
                np.array([1.0, 0.9, 0.5, 1.0, 0.3, 0.999], f32),
                np.array([0, 5, 0, 3, 8, 1], i32))
    if name == "qwen-vocab":  # a nucleus of tens of thousands of tokens
        return (rng.normal(size=(2, 151936)).astype(f32) * 2,
                np.array([0.7, 1.0], f32), np.array([0.9, 0.5], f32),
                np.zeros(2, i32))
    if name == "ties":  # few distinct values: the cuts fall inside ties
        logits = rng.integers(-3, 4, size=(5, 64)).astype(f32)
        return (logits, np.ones(5, f32),
                np.array([0.3, 0.6, 0.95, 1.0, 0.5], f32),
                np.array([0, 0, 0, 7, 20], i32))
    if name == "tiny-top-p":  # keeps only the top-1
        return (rng.normal(size=(3, 256)).astype(f32),
                np.array([0.5, 1.0, 2.0], f32), np.full(3, 1e-4, f32),
                np.zeros(3, i32))
    if name == "top-k-over-vocab":
        return (rng.normal(size=(3, 48)).astype(f32),
                np.ones(3, f32), np.array([1.0, 0.8, 1.0], f32),
                np.array([48, 49, 1000], i32))
    if name == "neg-inf":  # masked entries, some rows with few finite ones
        logits = rng.normal(size=(4, 40)).astype(f32) * 2
        logits[:, ::3] = -np.inf
        logits[3, 5:] = -np.inf
        return (logits, np.array([1.0, 0.8, 1.0, 1.0], f32),
                np.array([0.9, 1.0, 0.7, 0.99], f32),
                np.array([0, 30, 25, 3], i32))
    if name == "all-greedy":  # unscaled rows, filters as each row sets
        return (rng.normal(size=(4, 32)).astype(f32) * 3,
                np.zeros(4, f32), np.array([1.0, 0.9, 0.5, 1.0], f32),
                np.array([0, 0, 4, 6], i32))
    assert name == "all-disabled"
    return (rng.normal(size=(4, 32)).astype(f32) * 3,
            np.array([0.0, 0.5, 1.0, 1.5], f32), np.ones(4, f32),
            np.zeros(4, i32))


KEPT_CASES = ["small", "qwen-vocab", "ties", "tiny-top-p",
              "top-k-over-vocab", "neg-inf", "all-greedy", "all-disabled"]


@pytest.mark.parametrize("case", KEPT_CASES)
def test_top_k_top_p_match_numpy_reference(case):
    """The bisected cuts keep exactly the float64 sort definition's set."""
    logits, temperature, top_p, top_k = _kept_case(case)
    got = np.isfinite(np.asarray(sampling.filter_logits(
        logits, temperature, top_p, top_k)))
    want = _numpy_filter(logits, temperature, top_p, top_k)
    np.testing.assert_array_equal(got, want)
    if case == "tiny-top-p":
        assert (want.sum(axis=1) == 1).all()
    if case == "all-disabled":
        assert want.all()


def test_sampled_tokens_unchanged_and_counted(cfg_params, prompts):
    """`sample_tokens` draws what `jax.random.categorical` draws from the
    numpy reference's kept sets under the same keys (greedy rows: the raw
    argmax), and packed `step()["sampler"]` counts the rows that draw and
    that need each search, on a mixed and on an all-greedy batch."""
    rng = np.random.default_rng(3)
    logits = rng.normal(size=(6, 64)).astype(np.float32) * 3
    temperature = np.array([0.0, 0.7, 1.0, 0.8, 1.2, 0.0], np.float32)
    top_p = np.array([0.9, 0.9, 1.0, 0.5, 1.0, 1.0], np.float32)
    top_k = np.array([5, 0, 6, 10, 0, 0], np.int32)
    keys = sampling.request_keys(5, np.arange(6, dtype=np.int32),
                                 np.full(6, 2, np.int32))
    for temps in (temperature, np.zeros(6, np.float32)):
        kept = _numpy_filter(logits, temps, top_p, top_k)
        x = np.where(kept, np.asarray(sampling.scaled_logits(logits, temps)),
                     -np.inf)
        drawn = np.asarray(jax.vmap(jax.random.categorical)(keys, x))
        want = np.where(temps > 0, drawn, np.argmax(logits, axis=-1))
        got = np.asarray(sampling.sample_tokens(logits, temps, top_p, top_k,
                                                keys))
        np.testing.assert_array_equal(got, want)

    cfg, params = cfg_params
    # a greedy row's own filters never count: it does not draw
    mixed = [dict(top_p=0.5, top_k=3), dict(temperature=0.8, top_p=0.9),
             dict(temperature=1.0, top_k=5),
             dict(temperature=0.7, top_p=0.5, top_k=10)]
    for kinds, engaged in ((mixed, True), ([{}] * 4, False)):
        eng = build_engine(cfg, params)
        reqs = [Request(prompt=list(p), max_new_tokens=MAX_NEW, seed=i, **kw)
                for i, (p, kw) in enumerate(zip(prompts, kinds))]
        for r in reqs:
            eng.add_request(r)
        ran = {"draw": 0, "top_p": 0, "top_k": 0}
        while eng.sched.has_work:
            before = [r.num_generated for r in reqs]
            st = eng.step()["sampler"]
            drew = [r for r, n in zip(reqs, before) if r.num_generated > n]
            sampled = [r for r in drew if r.temperature > 0]
            assert st == {"sampled_rows": len(sampled),
                          "top_p_rows": sum(r.top_p < 1 for r in sampled),
                          "top_k_rows": sum(r.top_k > 0 for r in sampled)}
            for k in ("draw", "top_p", "top_k"):
                n = st["sampled_rows" if k == "draw" else f"{k}_rows"]
                ran[k] += int(n > 0)
        assert eng.sampler_stats == ran
        assert all(ran.values()) if engaged else not any(ran.values())


def test_request_keys_counter_stream():
    """Keys depend only on (seed, stream, draw index) — not position."""
    streams = np.array([3, 3, 5], np.int32)
    ngen = np.array([0, 1, 0], np.int32)
    k = np.asarray(jax.random.key_data(
        sampling.request_keys(42, streams, ngen)))
    assert not np.array_equal(k[0], k[1])  # same stream, different draw
    assert not np.array_equal(k[0], k[2])  # different stream
    k2 = np.asarray(jax.random.key_data(sampling.request_keys(
        42, np.array([5], np.int32), np.array([0], np.int32))))
    np.testing.assert_array_equal(k[2], k2[0])  # position-independent


def test_seeded_sampling_invariant_across_paths(cfg_params, prompts):
    """Pinned-seed sampled outputs are bit-identical across fused packed,
    two-dispatch packed, padded, and batch-composition changes."""
    cfg, params = cfg_params
    full_fused = drain(build_engine(cfg, params),
                       sampled_requests(prompts))
    full_2d = drain(build_engine(cfg, params, fused_sampling=False),
                    sampled_requests(prompts))
    full_padded = drain(build_engine(cfg, params, packed_attention=False),
                        sampled_requests(prompts))
    solo = drain(build_engine(cfg, params),
                 sampled_requests(prompts, subset=[1]))
    pair = drain(build_engine(cfg, params),
                 sampled_requests(prompts, subset=[3, 1]))
    assert full_fused == full_2d == full_padded
    assert solo[1001] == full_fused[1001]
    assert pair[1001] == full_fused[1001]
    assert pair[1003] == full_fused[1003]


# ---------------------------------------------------------------------------
# 3. async double-buffered loop
# ---------------------------------------------------------------------------


def test_stream_matches_sync_greedy(cfg_params, prompts):
    cfg, params = cfg_params
    res = run_requests(build_engine(cfg, params), prompts,
                       max_new_tokens=MAX_NEW)
    eng = build_engine(cfg, params)
    reqs = [Request(prompt=list(p), max_new_tokens=MAX_NEW)
            for p in prompts]
    by_req = stream_requests(eng, reqs)
    assert all(r.state is State.FINISHED for r in reqs)
    for r in reqs:  # yielded pairs ARE the outputs, in order
        assert by_req.get(r.req_id, []) == r.output
    for rs, ra in zip(res.requests, reqs):
        assert rs.output == ra.output
    assert eng.alloc.free_pages == eng.num_pages - 1, "pages leaked"
    assert set(eng.device_calls) == {"unified"}


def test_stream_matches_sync_seeded(cfg_params, prompts):
    cfg, params = cfg_params
    sync = drain(build_engine(cfg, params), sampled_requests(prompts))
    eng = build_engine(cfg, params)
    reqs = sampled_requests(prompts)
    stream_requests(eng, reqs)
    assert {r.seed: r.output for r in reqs} == sync


def test_stream_eos_on_prompt_completing_chunk(cfg_params):
    """EOS handling in the async loop, including a token sampled by a
    prompt-completing chunk under chunked prefill: finish lands (one step
    late) without corrupting outputs or leaking pages."""
    cfg, params = cfg_params
    rng = np.random.default_rng(3)
    prompts = make_prompts(cfg, rng, [24, 17, 6])

    # find the token each prompt's completion greedily samples, then make
    # it the EOS of a fresh engine run: requests must finish with exactly
    # that one token
    probe = run_requests(build_engine(cfg, params), prompts,
                         max_new_tokens=1)
    first = [r.output[0] for r in probe.requests]

    for i, eos in enumerate(first):
        eng = build_engine(cfg, params, enable_chunked_prefill=True,
                           max_prefill_tokens=8)
        reqs = [Request(prompt=list(p), max_new_tokens=MAX_NEW,
                        eos_token=eos if j == i else None)
                for j, p in enumerate(prompts)]
        by_req = stream_requests(eng, reqs)
        assert reqs[i].output == [eos], (i, reqs[i].output)
        assert by_req[reqs[i].req_id] == [eos]
        assert all(r.state is State.FINISHED for r in reqs)
        assert eng.alloc.free_pages == eng.num_pages - 1


def test_stream_with_preemption(cfg_params):
    """Page pressure under the async loop: preemption discards in-flight
    tokens (epoch bump) and the regenerated stream is bit-identical to an
    unpressured run."""
    cfg, params = cfg_params
    rng = np.random.default_rng(5)
    prompts = make_prompts(cfg, rng, [30, 28, 26, 24])
    roomy = drain(build_engine(cfg, params, num_pages=64),
                  sampled_requests(prompts))
    eng = build_engine(cfg, params, num_pages=14, max_seqs=2)
    reqs = sampled_requests(prompts)
    stream_requests(eng, reqs)
    # outputs identical despite the much smaller pool (any preemption the
    # pressure caused regenerated the same tokens from the same streams)
    assert {r.seed: r.output for r in reqs} == roomy
    assert eng.alloc.free_pages == eng.num_pages - 1


def test_run_drive_loop_and_callbacks(cfg_params, prompts):
    cfg, params = cfg_params
    eng = build_engine(cfg, params)
    reqs = [Request(prompt=list(p), max_new_tokens=MAX_NEW)
            for p in prompts]
    ids = [eng.submit(r) for r in reqs]
    seen_tokens: list[tuple[int, int]] = []
    finished: list[int] = []
    out = eng.run(on_token=lambda rid, tok: seen_tokens.append((rid, tok)),
                  on_finish=lambda req: finished.append(req.req_id))
    assert sorted(finished) == sorted(ids)
    assert out["unfinished"] == 0 and not out["exhausted"]
    grouped: dict[int, list[int]] = {}
    for rid, tok in seen_tokens:
        grouped.setdefault(rid, []).append(tok)
    for r in reqs:
        assert out["outputs"][r.req_id] == r.output
        assert grouped[r.req_id] == r.output
    assert eng.sched.on_finish is None  # callback uninstalled


def test_generate_warns_on_exhaustion(cfg_params, prompts, caplog):
    cfg, params = cfg_params
    eng = build_engine(cfg, params)
    reqs = [Request(prompt=list(p), max_new_tokens=MAX_NEW)
            for p in prompts]
    with caplog.at_level(logging.WARNING, logger="repro.serving.engine"):
        eng.generate(reqs, max_steps=2)
    assert eng.last_generate["exhausted"]
    assert eng.last_generate["unfinished"] == len(
        [r for r in reqs if r.state is not State.FINISHED]) > 0
    assert any("max_steps" in rec.message for rec in caplog.records)
    # and a completing run reports clean
    eng2 = build_engine(cfg, params)
    eng2.generate([Request(prompt=list(prompts[0]), max_new_tokens=2)])
    assert not eng2.last_generate["exhausted"]
    assert eng2.last_generate["unfinished"] == 0


def test_stream_overlap_telemetry(cfg_params, prompts):
    """The async loop records `overlap` phase spans and keeps the
    sampled-token counter exact (engine-reported, not decision-derived)."""
    from repro.obs.telemetry import Telemetry
    cfg, params = cfg_params
    tel = Telemetry()
    eng = build_engine(cfg, params, telemetry=tel)
    reqs = [Request(prompt=list(p), max_new_tokens=MAX_NEW)
            for p in prompts]
    stream_requests(eng, reqs)
    phase_h = tel.metrics.families()["repro_step_phase_seconds"]
    overlap = phase_h.get(phase="overlap")
    assert overlap is not None and overlap["count"] > 0, \
        "no overlap spans recorded"
    assert (tel.metrics.value("repro_tokens_total", kind="sampled")
            == sum(len(r.output) for r in reqs))
