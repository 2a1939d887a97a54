"""Telemetry-instrumented serving: end-to-end metric/trace consistency
and the telemetry -> autotune refit loop.

The serving suites prove scheduling features never change WHAT is
computed; this suite proves observing the engine doesn't either, and that
what the telemetry reports is re-derivable from engine ground truth
(`serving_harness.assert_telemetry_consistent`).  The refit test closes
the loop from the ISSUE: a mixed chunked trace -> latency grid ->
`refit_from_telemetry` -> a heuristics file `heuristics.load` accepts.
"""
import glob
import json
import os
import re

import jax
import numpy as np
import pytest

import serving_harness as H
from repro.autotune.tune import refit_from_telemetry
from repro.core.attention import heuristics
from repro.obs import FakeClock, Telemetry
from repro.serving.request import make_requests


@pytest.fixture(scope="module")
def smollm():
    return H.build_cfg_params()


# one letter per engine span; a step's spans, in time order, spell a
# word of its path's pattern (the padded path runs one pack/dispatch/
# consume per executable kind, and only a prompt-completing chunk or a
# decode samples)
SPAN_LETTERS = {"repro.schedule": "S", "repro.pack": "P",
                "repro.compile": "C", "repro.dispatch": "X",
                "repro.prefix_index": "I", "repro.wait_tokens": "W",
                "repro.consume": "K", "repro.finish": "F"}
PACKED_STEP = r"S(PC?X)?I?(WK)?F"
PATHS = {
    "async": ({}, PACKED_STEP),
    "sync": ({"fused_sampling": False}, PACKED_STEP),
    "padded": ({"packed_attention": False}, r"SI?(PXW?K)*F"),
}


def _engine_spans(log_dir) -> list[tuple[str, int, int, int]]:
    """(name, start ns, end ns, step) of every `repro.*` span in the
    profile under `log_dir`, in start order."""
    from jax.profiler import ProfileData
    [path] = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                       recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    out.append((ev.name, int(ev.start_ns),
                                int(ev.start_ns + ev.duration_ns),
                                dict(ev.stats)["step"]))
    return sorted(out, key=lambda s: (s[1], -s[2]))


@pytest.mark.parametrize("path", sorted(PATHS))
def test_stream_phase_spans_in_profiler_trace(smollm, tmp_path, monkeypatch,
                                              path):
    """Without telemetry `stream()` writes its phase spans into a
    `jax.profiler` trace on every path (the double-buffered packed loop,
    the synchronous packed step, the padded per-kind step): each finished
    step has its spans, tagged with its id, in phase order and never
    overlapping.  It calls no `block_until_ready`, and tracing changes no
    token."""
    cfg, params = smollm
    kw, pattern = PATHS[path]
    prompts = H.make_prompts(cfg, np.random.default_rng(11), (14, 6, 21, 9))

    def run():
        eng = H.build_engine(cfg, params, max_seqs=2,
                             enable_chunked_prefill=True,
                             enable_prefix_caching=True,
                             max_prefill_tokens=16, **kw)
        reqs = make_requests(prompts, max_new_tokens=5)
        for r in reqs:
            eng.submit(r)
        list(eng.stream())
        return eng, [r.output for r in reqs]

    _, plain = run()
    syncs = []
    sync = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: syncs.append(1) or sync(x))
    with jax.profiler.trace(str(tmp_path)):
        eng, traced = run()
    assert traced == plain
    assert syncs == []

    spans = _engine_spans(str(tmp_path))
    steps: dict[int, list] = {}
    for sp in spans:
        steps.setdefault(sp[3], []).append(sp)
    assert sorted(steps) == list(range(eng.step_idx))
    for step, ss in steps.items():
        word = "".join(SPAN_LETTERS[n] for n, *_ in ss)
        assert re.fullmatch(pattern, word), (step, word)
    assert {"repro.dispatch", "repro.wait_tokens"} <= {n for n, *_ in spans}
    # one thread drives every step: no two spans overlap
    for a, b in zip(spans, spans[1:]):
        assert a[2] <= b[1], (a, b)


@pytest.fixture(scope="module")
def chunked_run(smollm):
    """One mixed chunked-prefill trace with full telemetry, shared by the
    consistency / exposition / refit tests (compiles are the expensive
    part; drain once)."""
    cfg, params = smollm
    rng = np.random.default_rng(7)
    # interval=1: time every launch so the latency grid sees every warm
    # launch (production default samples every 8th to keep overhead <5%)
    tel = Telemetry(launch_timing_interval=1)
    eng = H.build_engine(cfg, params, max_seqs=4, num_pages=96,
                         enable_chunked_prefill=True,
                         enable_prefix_caching=True,
                         max_prefill_tokens=16, telemetry=tel)
    res = H.run_requests(eng, H.make_prompts(cfg, rng, (20, 11, 26, 9, 17)),
                         max_new_tokens=6)
    return res


def test_telemetry_consistent_with_engine(chunked_run):
    H.assert_telemetry_consistent(chunked_run)


def test_prometheus_exposition_of_serving_run(chunked_run):
    text = chunked_run.engine.telemetry.prometheus_text()
    # step-phase histograms for every block_until_ready-bounded region
    for phase in ("schedule", "pack", "launch", "sample", "host"):
        assert f'repro_step_phase_seconds_bucket{{phase="{phase}"' in text
    # queue/pool gauges and cache/scheduler counters made it out
    assert 'repro_queue_depth{queue="waiting"}' in text
    assert 'repro_pool_pages{state="free"}' in text
    assert 'repro_scheduler_events_total{event="admitted"}' in text
    assert 'repro_cache_events_total{event="' in text
    assert "repro_step_seconds_bucket" in text
    assert "repro_request_ttft_seconds_count" in text


def test_snapshot_and_summary(chunked_run, tmp_path):
    tel = chunked_run.engine.telemetry
    path = tmp_path / "metrics.jsonl"
    tel.write_snapshot(str(path), arch="smollm-135m")
    [line] = tel.metrics.read_jsonl(str(path))
    assert line["meta"] == {"arch": "smollm-135m"}
    assert (line["metrics"]["repro_steps_total"]["series"][0]["value"]
            == chunked_run.num_steps)
    s = tel.summary()
    assert s["finished"] == len(chunked_run.requests)
    assert s["ttft_p50"] > 0 and s["step_p50"] > 0
    assert 0.0 <= s["padding_waste"] < 1.0


def test_trace_export_is_perfetto_loadable(chunked_run, tmp_path):
    path = tmp_path / "trace.json"
    chunked_run.engine.telemetry.export_trace(str(path))
    doc = json.load(open(path))
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert any(e["ph"] == "M" and e["name"] == "process_name" for e in evs)
    # per-request lifecycle tracks alongside the engine step track
    tracks = {e["args"]["name"] for e in evs if e["name"] == "thread_name"}
    assert "engine" in tracks
    assert any(t.startswith("req-") for t in tracks)
    assert sum(e["name"] == "step" for e in evs) == chunked_run.num_steps
    assert all(e["dur"] >= 0 for e in evs if e["ph"] == "X")


def test_latency_grid_refit_and_heuristics_load(chunked_run, tmp_path):
    tel = chunked_run.engine.telemetry
    grid = tel.latency_grid()
    # chunked prefill re-lands on the same token buckets, so the trace
    # must contain warm (post-capture) unified launches
    assert any(e["phase"] == "unified" for e in grid["entries"])
    assert all(e["count"] >= 1 and e["mean_s"] > 0
               for e in grid["entries"])
    grid_path = tmp_path / "latency_grid.json"
    tel.export_latency_grid(str(grid_path))

    out_json = tmp_path / "refit.json"
    out_py = tmp_path / "refit.py"
    rep = refit_from_telemetry(str(grid_path), str(out_json), str(out_py))
    st = rep["phases"]["unified"]
    assert st["profiles"] >= 1 and st["observed_points"] >= 1
    assert st["calibration_ratio"] > 0
    assert rep["payload"]["unified_tree"], "refit produced no unified tree"

    try:  # the exported file is a drop-in heuristics tree
        heuristics.load(str(out_json))
        assert heuristics.loaded_path() == str(out_json)
    finally:
        heuristics.reset()


def test_telemetry_does_not_change_outputs(smollm):
    cfg, params = smollm
    rng = np.random.default_rng(3)
    prompts = H.make_prompts(cfg, rng, (14, 6, 21))
    plain = H.run_requests(H.build_engine(cfg, params), prompts,
                           max_new_tokens=5)
    observed = H.run_requests(
        H.build_engine(cfg, params, telemetry=Telemetry()), prompts,
        max_new_tokens=5)
    H.assert_same_outputs(plain, observed, label_a="plain",
                          label_b="telemetry")
    H.assert_telemetry_consistent(observed)


def test_padded_engine_telemetry(smollm):
    """The padded per-kind step instruments too: per-kind launch/compile
    histograms and the same cross-checked counters."""
    cfg, params = smollm
    rng = np.random.default_rng(5)
    res = H.run_requests(
        H.build_engine(cfg, params, packed_attention=False,
                       telemetry=Telemetry(clock=FakeClock(tick=1e-4))),
        H.make_prompts(cfg, rng, (12, 7)), max_new_tokens=4)
    H.assert_telemetry_consistent(res)
    snap = res.engine.telemetry.metrics.snapshot()
    kinds = {s["labels"]["kind"] for s
             in snap["repro_compile_events_total"]["series"]}
    assert "decode" in kinds and any("prefill" in k for k in kinds)


# ---------------------------------------------------------------------------
# device-side timing: XLA cost_analysis in the grid, host-overhead refit
# ---------------------------------------------------------------------------


def test_latency_grid_carries_device_cost(chunked_run):
    """Every grid entry carries the executable's XLA cost_analysis
    (flops + bytes), the refit's device-time floor."""
    grid = chunked_run.engine.telemetry.latency_grid()
    assert grid["entries"]
    for e in grid["entries"]:
        assert e["flops"] and e["flops"] > 0, e
        assert e["bytes_accessed"] and e["bytes_accessed"] > 0, e


def test_refit_separate_host_overhead(chunked_run, tmp_path):
    """`separate_host_overhead=True` reports a host-overhead estimate
    and folds it into calibration; the default reports the diagnostic
    but calibrates on raw wall-clock."""
    grid = chunked_run.engine.telemetry.latency_grid()
    out = tmp_path / "refit_host.json"
    rep = refit_from_telemetry(grid, str(out),
                               separate_host_overhead=True)
    st = rep["phases"]["unified"]
    assert st["host_overhead_s_est"] is not None
    assert st["host_overhead_s_est"] >= 0
    assert 0.0 < st["device_time_fraction"] <= 1.0
    assert st["host_overhead_applied_s"] == st["host_overhead_s_est"]
    assert st["calibration_ratio"] > 0
    try:
        heuristics.load(str(out))  # still a drop-in tree
    finally:
        heuristics.reset()
    rep_raw = refit_from_telemetry(grid, str(tmp_path / "refit_raw.json"))
    st_raw = rep_raw["phases"]["unified"]
    assert st_raw["host_overhead_applied_s"] == 0.0
    assert st_raw["host_overhead_s_est"] == st["host_overhead_s_est"]


# ---------------------------------------------------------------------------
# online refit daemon: hot-swap between steps, token identity
# ---------------------------------------------------------------------------


def test_refit_daemon_hot_swaps_token_identically(smollm, tmp_path):
    """The full online loop on the engine hook: watch -> refit -> hot-
    swap, with the emitted tokens EXACTLY those of an unobserved run —
    the swap may only re-route dispatch."""
    from repro.obs import RefitDaemon

    cfg, params = smollm
    rng = np.random.default_rng(9)
    prompts = H.make_prompts(cfg, rng, (18, 7, 24, 11))
    heuristics.reset()
    plain = H.run_requests(H.build_engine(cfg, params), prompts,
                           max_new_tokens=10)
    tel = Telemetry(launch_timing_interval=1)
    daemon = RefitDaemon(tel, out_dir=str(tmp_path), min_new=3)
    try:
        live = H.run_requests(
            H.build_engine(cfg, params, telemetry=tel, refit=daemon),
            prompts, max_new_tokens=10)
    finally:
        heuristics.reset()
    rep = daemon.report()
    assert rep["refits"] >= 1 and rep["swaps"] >= 1
    assert all(s is not None for s in rep["swap_steps"])
    # swaps happen at step boundaries within the run
    assert max(rep["swap_steps"]) <= live.num_steps
    assert (tmp_path / "refit-000.json").exists()
    import json as _json
    raw = _json.loads((tmp_path / "refit-000.json").read_text())
    # the packed engine's grid is all unified-phase launches
    assert raw["unified_tree"], "refit artifact has no unified tree"
    H.assert_same_outputs(plain, live, label_a="plain",
                          label_b="online-refit")
    assert tel.metrics.value("repro_refit_swaps_total") == rep["swaps"]
    # the hot-swap left its mark on the trace for post-hoc audit
    assert any(e["name"] == "heuristics_hot_swap"
               for e in tel.tracer.events())


def test_forced_hot_swap_reroutes_dispatch_not_tokens(smollm, tmp_path):
    """Differential guard from the ISSUE: a mid-run tree swap that
    FORCES a different kernel variant changes `Engine.dispatch_counts`
    routing — and nothing else.  Uses the same `load_payload` plumbing
    the daemon's `apply_pending` calls between steps."""
    from repro.serving.request import make_requests

    cfg, params = smollm
    rng = np.random.default_rng(13)
    prompts = H.make_prompts(cfg, rng, (16, 8, 22))
    heuristics.reset()
    plain = H.run_requests(H.build_engine(cfg, params), prompts,
                           max_new_tokens=8)
    # a tree that routes EVERY unified launch to the segmented variant
    # (the defaults pick gqa for this geometry)
    seg = {"variant": "segmented", "tile": None, "num_segments": 2,
           "block_q": 16}
    payload = {"decode_tree": [[{}, seg]], "prefill_tree": [[{}, seg]],
               "unified_tree": [[{}, seg]]}
    eng = H.build_engine(cfg, params)
    reqs = make_requests([list(p) for p in prompts], max_new_tokens=8)
    for r in reqs:
        eng.add_request(r)
    swap_at, steps = 4, 0
    try:
        while eng.sched.has_work:
            if steps == swap_at:  # step boundary: the daemon's swap point
                heuristics.load_payload(payload, source="<forced>")
            eng.step()
            steps += 1
    finally:
        heuristics.reset()
    variants = {v for (ph, v) in eng.dispatch_counts if ph == "unified"}
    assert variants == {"gqa", "segmented"}, (
        f"swap at step {swap_at} should split routing, got {variants}: "
        f"{dict(eng.dispatch_counts)}")
    for i, (ra, rb) in enumerate(zip(plain.requests, reqs)):
        assert ra.output == rb.output, (
            f"request {i}: forced variant swap changed tokens\n"
            f"  plain:   {ra.output}\n  swapped: {rb.output}")
