"""Observability primitives: metrics registry, tracer, request tracker.

Pure-host unit tests — no engine, no jax.  Everything timestamped runs on
a FakeClock or explicit `t=` arguments, so lifecycle math (TTFT, ITL,
queue time across preemptions) is asserted exactly, not approximately.
"""
import dataclasses
import json
import math
from types import SimpleNamespace

import pytest

from repro.obs import (FakeClock, Registry, RequestTracker, Telemetry,
                       Tracer, parse_prometheus, pow2_buckets)
from repro.obs.metrics import fmt_float


# ---------------------------------------------------------------------------
# buckets + rendering helpers
# ---------------------------------------------------------------------------


def test_pow2_buckets():
    assert pow2_buckets(1.0, 8.0) == (1.0, 2.0, 4.0, 8.0)
    assert pow2_buckets(1e-6, 128.0)[0] == 1e-6
    assert pow2_buckets(1.0, 5.0) == (1.0, 2.0, 4.0, 8.0)  # doubles past hi
    with pytest.raises(AssertionError):
        pow2_buckets(0.0, 1.0)


def test_fmt_float():
    assert fmt_float(math.inf) == "+Inf"
    assert fmt_float(-math.inf) == "-Inf"
    assert fmt_float(4.0) == "4"
    assert fmt_float(0.25) == "0.25"


# ---------------------------------------------------------------------------
# counters / gauges
# ---------------------------------------------------------------------------


def test_counter_inc_and_labels():
    r = Registry()
    c = r.counter("hits_total", "hits", labelnames=("kind",))
    c.inc(kind="a")
    c.inc(2.5, kind="a")
    c.inc(kind="b")
    assert c.value(kind="a") == 3.5
    assert c.value(kind="b") == 1.0
    assert c.value(kind="missing") == 0.0  # untouched series read as 0
    assert r.value("hits_total", kind="a") == 3.5
    with pytest.raises(AssertionError):
        c.inc(-1.0, kind="a")  # counters are monotone
    with pytest.raises(ValueError):
        c.inc(wrong_label="a")  # label names are declared, not ad hoc


def test_gauge_set_inc_dec():
    g = Registry().gauge("depth", "", labelnames=("q",))
    g.set(4, q="waiting")
    g.inc(2, q="waiting")
    g.dec(q="waiting")
    assert g.value(q="waiting") == 5.0


def test_registry_get_or_create_and_type_conflicts():
    r = Registry()
    a = r.counter("x_total", labelnames=("k",))
    assert r.counter("x_total", labelnames=("k",)) is a
    with pytest.raises(ValueError):
        r.gauge("x_total", labelnames=("k",))  # kind mismatch
    with pytest.raises(ValueError):
        r.counter("x_total", labelnames=("other",))  # label mismatch


# ---------------------------------------------------------------------------
# histograms
# ---------------------------------------------------------------------------


def test_histogram_bucketing_le_inclusive():
    h = Registry().histogram("lat", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.0, 1.5, 4.0, 100.0):  # bound values land IN the bucket
        h.observe(v)
    got = h.get()
    assert got["count"] == 5
    assert got["sum"] == pytest.approx(107.0)
    # cumulative counts per le-bound, overflow in +Inf
    assert got["buckets"] == {"1": 2, "2": 3, "4": 4, "+Inf": 5}


def test_histogram_quantile_interpolation():
    h = Registry().histogram("lat", buckets=(1.0, 2.0, 4.0))
    for _ in range(4):
        h.observe(1.5)  # all mass in (1, 2]
    assert h.quantile(0.5) == pytest.approx(1.5)  # midpoint of the bucket
    assert h.quantile(1.0) == pytest.approx(2.0)
    assert Registry().histogram("empty").quantile(0.5) is None
    over = Registry().histogram("over", buckets=(1.0, 2.0))
    over.observe(50.0)
    assert over.quantile(0.99) == 2.0  # overflow clamps to largest bound


def test_histogram_labeled_series_independent():
    h = Registry().histogram("lat", labelnames=("phase",), buckets=(1.0,))
    h.observe(0.5, phase="pack")
    h.observe(0.7, phase="launch")
    assert h.get(phase="pack")["count"] == 1
    assert h.get(phase="launch")["count"] == 1
    assert h.get(phase="sample") is None


# ---------------------------------------------------------------------------
# cardinality cap
# ---------------------------------------------------------------------------


def test_label_cardinality_cap_drops_and_counts():
    r = Registry(max_series_per_family=2)
    c = r.counter("req_total", labelnames=("req_id",))
    c.inc(req_id="1")
    c.inc(req_id="2")
    c.inc(req_id="3")  # past the cap: dropped, counted, no growth
    c.inc(req_id="4")
    assert len(c) == 2
    assert c.dropped == 2
    assert r.dropped_series == 2
    assert c.value(req_id="3") == 0.0
    c.inc(req_id="1")  # existing series still updatable past the cap
    assert c.value(req_id="1") == 2.0


# ---------------------------------------------------------------------------
# exports
# ---------------------------------------------------------------------------


def _populated_registry() -> Registry:
    r = Registry()
    r.counter("repro_hits_total", "hits by kind",
              labelnames=("kind",)).inc(3, kind='we"ird\nlabel')
    r.gauge("repro_depth", "queue depth").set(7)
    h = r.histogram("repro_lat_seconds", "latency", buckets=(0.5, 1.0))
    h.observe(0.25)
    h.observe(2.0)
    return r


def test_prometheus_exposition_format():
    text = _populated_registry().render_prometheus()
    assert "# TYPE repro_hits_total counter" in text
    assert "# HELP repro_hits_total hits by kind" in text
    # label values escaped: backslash-n and backslash-quote
    assert 'repro_hits_total{kind="we\\"ird\\nlabel"} 3' in text
    assert "# TYPE repro_depth gauge" in text
    assert "repro_depth 7" in text
    # histograms render cumulative buckets + sum + count, +Inf last
    assert 'repro_lat_seconds_bucket{le="0.5"} 1' in text
    assert 'repro_lat_seconds_bucket{le="1"} 1' in text
    assert 'repro_lat_seconds_bucket{le="+Inf"} 2' in text
    assert "repro_lat_seconds_sum 2.25" in text
    assert "repro_lat_seconds_count 2" in text


def test_snapshot_json_roundtrip_and_jsonl(tmp_path):
    r = _populated_registry()
    snap = r.snapshot()
    assert json.loads(json.dumps(snap)) == snap  # pure JSON, exact
    path = tmp_path / "m.jsonl"
    r.write_jsonl(str(path), step=1)
    r.write_jsonl(str(path), step=2)
    lines = Registry.read_jsonl(str(path))
    assert [ln["meta"]["step"] for ln in lines] == [1, 2]
    assert lines[0]["metrics"] == snap


# ---------------------------------------------------------------------------
# clock + tracer
# ---------------------------------------------------------------------------


def test_fake_clock_deterministic():
    clk = FakeClock(start=10.0, tick=0.5)
    assert [clk.now(), clk.now()] == [10.0, 10.5]
    clk.advance(4.0)
    assert clk.now() == 15.0


def test_tracer_chrome_trace_shape():
    tr = Tracer(clock=FakeClock(), process_name="test-proc")
    tr.complete("step", 1.0, 1.25, track="engine", tokens=4)
    tr.instant("first_token", 1.1, track="req-0")
    with tr.span("pack", track="engine"):
        pass
    doc = tr.to_json()
    evs = doc["traceEvents"]
    # metadata first: process name + one thread_name per named track
    assert evs[0] == {"name": "process_name", "ph": "M", "pid": 1,
                      "tid": 0, "args": {"name": "test-proc"}}
    tracks = {e["args"]["name"] for e in evs if e["name"] == "thread_name"}
    assert tracks == {"engine", "req-0"}
    step = next(e for e in evs if e["name"] == "step")
    assert step["ph"] == "X"
    assert step["ts"] == pytest.approx(1.0e6)
    assert step["dur"] == pytest.approx(0.25e6)
    assert step["args"] == {"tokens": 4}
    assert json.loads(json.dumps(doc)) == doc


def test_tracer_capacity_bound():
    tr = Tracer(capacity=3)
    for i in range(5):
        tr.complete(f"e{i}", 0.0, 1.0)
    assert len(tr) == 3
    assert tr.dropped == 2


# ---------------------------------------------------------------------------
# request lifecycle math (explicit timestamps -> exact assertions)
# ---------------------------------------------------------------------------


def _req(i, prompt_len=5):
    """A request as `Scheduler.add` leaves it: submitted and queued at 0."""
    return SimpleNamespace(req_id=i, prompt=list(range(prompt_len)),
                           submitted_at=0.0, queued_at=0.0, admitted_at=None,
                           queue_wait=0.0, preemptions=0)


def _admit(trk, r, t):
    """Stamp an admission at t as the scheduler does, then report it."""
    wait = t - r.queued_at
    r.queue_wait += wait
    r.queued_at = None
    if r.admitted_at is None:
        r.admitted_at = t
    trk.admit(r, wait)


def _preempt(trk, r, t):
    r.preemptions += 1
    r.queued_at = t
    trk.preempt(r)


def test_request_lifecycle_ttft_itl_queue():
    reg = Registry()
    trk = RequestTracker(reg, Tracer(clock=FakeClock()))
    r = _req(0, prompt_len=7)
    rec = trk.submit(r)
    _admit(trk, r, t=2.0)     # admission: 2s queued
    trk.chunk(r)
    trk.token(r, t=3.0)       # first token
    trk.token(r, t=4.0)       # itl 1.0
    _preempt(trk, r, t=5.0)   # back to the waiting queue
    _admit(trk, r, t=9.0)     # re-admission: +4s queued
    trk.token(r, t=9.0)       # itl 5.0
    trk.finish(r, t=10.0)

    assert rec.prompt_tokens == 7
    assert rec.ttft == pytest.approx(3.0)
    assert rec.e2e == pytest.approx(10.0)
    assert rec.admit_t == pytest.approx(2.0)  # the first admission
    assert rec.queue_time == pytest.approx(6.0)
    assert rec.num_tokens == 3
    assert rec.preemptions == 1
    assert reg.value("repro_request_events_total", event="token") == 3
    assert reg.value("repro_request_events_total", event="preempted") == 1
    s = trk.summary()
    assert s["requests"] == s["finished"] == 1
    assert s["tokens"] == 3 and s["preemptions"] == 1
    # histograms saw the same milestones (bucketed, so bound-level checks)
    assert reg.families()["repro_request_ttft_seconds"].get()["count"] == 1
    assert reg.families()["repro_request_itl_seconds"].get()["count"] == 2
    assert reg.families()["repro_request_queue_seconds"].get()["count"] == 2


def test_request_tracker_unknown_request_is_noop():
    trk = RequestTracker(Registry())
    trk.token(_req(99), t=1.0)  # never submitted: ignored, no crash
    trk.finish(_req(99), t=2.0)
    assert trk.records == {}


# ---------------------------------------------------------------------------
# telemetry facade: phases, launches, the latency grid
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _Profile:  # stand-in for engine.BatchProfile (astuple-compatible)
    num_seqs: int = 4
    max_context: int = 64
    group: int = 4
    page_size: int = 16
    decode_share: float = 0.5
    avg_query_len: int = 8
    total_tokens: int = 32


_KCFG = SimpleNamespace(variant="fused", tile=128, num_segments=1,
                        block_q=16)


def test_telemetry_phases_and_launch_split():
    tel = Telemetry(clock=FakeClock(tick=0.01))
    with tel.phase("pack", tokens=32):
        pass
    p, k = _Profile(), _KCFG
    tel.record_launch("unified", p, k, 0.0, 1.0, compiled=True, tokens=32)
    tel.record_launch("unified", p, k, 2.0, 2.1, compiled=False, tokens=32)
    m = tel.metrics
    assert m.value("repro_compile_events_total", kind="unified") == 1
    fam = m.families()
    assert fam["repro_compile_seconds"].get(kind="unified")["count"] == 1
    assert fam["repro_launch_seconds"].get(kind="unified")["count"] == 1
    assert fam["repro_step_phase_seconds"].get(phase="pack")["count"] == 1
    assert fam["repro_step_phase_seconds"].get(phase="launch")["count"] == 2


def test_sampled_launch_timing():
    """Warm launches are only timed every Nth call; untimed launches
    still count compiles and trace, but never feed histograms/grid."""
    tel = Telemetry(launch_timing_interval=4)
    assert [tel.time_this_launch() for _ in range(8)] == \
        [False, False, False, True] * 2
    p, k = _Profile(), _KCFG
    tel.record_launch("unified", p, k, 0.0, 0.1, compiled=False,
                      tokens=32, timed=False)
    tel.record_launch("unified", p, k, 0.0, 0.1, compiled=True,
                      tokens=32, timed=False)
    assert tel.latency_grid()["entries"] == []
    fam = tel.metrics.families()
    assert fam["repro_launch_seconds"].get(kind="unified") is None
    assert fam["repro_compile_seconds"].get(kind="unified") is None
    # compile COUNT is exact regardless of timing sampling
    assert tel.metrics.value("repro_compile_events_total",
                             kind="unified") == 1
    assert len(tel.tracer) == 2
    assert all(not e["args"]["timed"] for e in tel.tracer.events())
    # interval=1 (the test default elsewhere) times everything
    always = Telemetry(launch_timing_interval=1)
    assert all(always.time_this_launch() for _ in range(3))


def test_latency_grid_excludes_compiles_and_aggregates():
    tel = Telemetry()
    tel.set_arch(num_q_heads=16, num_kv_heads=4, head_dim=64, page_size=16)
    p, k = _Profile(), _KCFG
    tel.record_launch("unified", p, k, 0.0, 5.0, compiled=True, tokens=32)
    tel.record_launch("unified", p, k, 0.0, 0.2, compiled=False, tokens=32)
    tel.record_launch("unified", p, k, 0.0, 0.4, compiled=False, tokens=32)
    tel.record_launch("decode", None, k, 0.0, 0.1, compiled=False, tokens=4)
    grid = tel.latency_grid()
    assert grid["arch"]["num_q_heads"] == 16
    [e] = grid["entries"]  # compile + profile-less launches excluded
    assert e["phase"] == "unified"
    assert e["count"] == 2
    assert e["mean_s"] == pytest.approx(0.3)
    assert e["min_s"] == pytest.approx(0.2)
    assert e["max_s"] == pytest.approx(0.4)
    assert e["profile"]["total_tokens"] == 32
    assert e["config"] == {"variant": "fused", "tile": 128,
                           "num_segments": 1, "block_q": 16}


def test_latency_grid_carries_launch_cost():
    """XLA cost_analysis rides into the grid (first-seen-wins) so the
    refit can separate host overhead from device time."""
    tel = Telemetry()
    p, k = _Profile(), _KCFG
    tel.record_launch("unified", p, k, 0.0, 0.2, compiled=False, tokens=32)
    tel.record_launch("unified", p, k, 0.0, 0.3, compiled=False, tokens=32,
                      cost={"flops": 1e9, "bytes_accessed": 2e6})
    tel.record_launch("unified", p, k, 0.0, 0.4, compiled=False, tokens=32,
                      cost={"flops": 9e9, "bytes_accessed": 9e6})  # ignored
    [e] = tel.latency_grid()["entries"]
    assert e["count"] == 3
    assert e["flops"] == pytest.approx(1e9)
    assert e["bytes_accessed"] == pytest.approx(2e6)
    assert tel.grid_counts() == {("unified", dataclasses.astuple(p)): 3}


# ---------------------------------------------------------------------------
# Prometheus exposition conformance (text format v0.0.4)
# ---------------------------------------------------------------------------


def test_label_escaping_conformance():
    """Label values escape backslash, double-quote and newline — and
    escape backslashes FIRST, so a literal `\\n` in a value does not
    collapse with a real newline's `\\n` escape."""
    r = Registry()
    c = r.counter("esc_total", "t", labelnames=("v",))
    tricky = 'back\\slash "quoted"\nnewline and a literal \\n'
    c.inc(5, v=tricky)
    text = r.render_prometheus()
    line = next(ln for ln in text.splitlines()
                if ln.startswith("esc_total{"))
    assert line == ('esc_total{v="back\\\\slash \\"quoted\\"\\nnewline '
                    'and a literal \\\\n"} 5')
    # the escaped line is single-line (the raw newline never leaks)
    assert "\n" not in line
    # and unescaping round-trips exactly
    fam = parse_prometheus(text)["esc_total"]
    [(_, labels, value)] = fam["samples"]
    assert labels == {"v": tricky}
    assert value == 5.0


def test_help_escaping_conformance():
    """HELP text escapes only backslash and newline; double quotes are
    legal verbatim in HELP (unlike label values)."""
    r = Registry()
    r.gauge("g", 'help with "quotes", a \\ and\na newline').set(1)
    text = r.render_prometheus()
    help_line = next(ln for ln in text.splitlines()
                     if ln.startswith("# HELP g "))
    assert help_line == ('# HELP g help with "quotes", a \\\\ and\\n'
                         'a newline')
    assert parse_prometheus(text)["g"]["help"] == \
        'help with "quotes", a \\ and\na newline'


def test_histogram_exposition_contract():
    """The histogram sample contract scrapers rely on: cumulative
    `le`-bucket counts ending in an `+Inf` bucket that equals `_count`,
    plus `_sum`, all in the same family."""
    r = Registry()
    h = r.histogram("lat_seconds", "lat", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 3.0):
        h.observe(v)
    fam = parse_prometheus(r.render_prometheus())["lat_seconds"]
    assert fam["type"] == "histogram"
    buckets = {labels["le"]: value for name, labels, value in fam["samples"]
               if name == "lat_seconds_bucket"}
    [count] = [v for n, _, v in fam["samples"] if n == "lat_seconds_count"]
    [total] = [v for n, _, v in fam["samples"] if n == "lat_seconds_sum"]
    assert buckets == {"0.1": 1.0, "1": 2.0, "+Inf": 3.0}
    # cumulative: monotone in le order, +Inf bucket == _count
    assert buckets["0.1"] <= buckets["1"] <= buckets["+Inf"] == count == 3.0
    assert total == pytest.approx(3.55)


def test_parse_prometheus_roundtrip_full_registry():
    """render -> parse -> every sample matches the registry's state."""
    r = _populated_registry()
    fams = parse_prometheus(r.render_prometheus())
    assert set(fams) == {"repro_hits_total", "repro_depth",
                         "repro_lat_seconds"}
    assert fams["repro_hits_total"]["type"] == "counter"
    [(_, labels, value)] = fams["repro_hits_total"]["samples"]
    assert labels == {"kind": 'we"ird\nlabel'} and value == 3.0
    assert fams["repro_depth"]["samples"] == [("repro_depth", {}, 7.0)]
    got = {(n, labels.get("le")): v for n, labels, v
           in fams["repro_lat_seconds"]["samples"]}
    want = r.families()["repro_lat_seconds"].get()
    assert got[("repro_lat_seconds_count", None)] == want["count"]
    assert got[("repro_lat_seconds_sum", None)] == want["sum"]
    for le, n in want["buckets"].items():
        assert got[("repro_lat_seconds_bucket", le)] == n


def test_parse_prometheus_rejects_malformed():
    parse_prometheus("ok_total 1\n")  # baseline: this parses
    for bad in ("no_value\n", 'unclosed{a="b 1\n', "name 1 2 3 extra\n"):
        with pytest.raises(ValueError):
            parse_prometheus(bad)


# ---------------------------------------------------------------------------
# tracer ring mode + flight recorder
# ---------------------------------------------------------------------------


def test_tracer_ring_mode_keeps_tail():
    tr = Tracer(capacity=3, ring=True)
    for i in range(5):
        tr.complete(f"e{i}", float(i), float(i) + 1.0)
    assert len(tr) == 3
    assert tr.dropped == 2  # overwrites are counted like drops
    assert [e["name"] for e in tr.events()] == ["e2", "e3", "e4"]
    # to_json keeps metadata even after eviction
    assert tr.to_json()["traceEvents"][0]["name"] == "process_name"


def test_telemetry_surfaces_dropped_trace_events():
    tel = Telemetry(trace_ring=True, trace_capacity=2)
    for i in range(5):
        tel.tracer.complete(f"e{i}", 0.0, 1.0)
    assert tel.tracer.dropped == 3
    assert tel.summary()["trace_dropped_events"] == 3  # ring overwrites
    bounded = Telemetry(trace_capacity=2)
    for i in range(5):
        bounded.tracer.complete(f"e{i}", 0.0, 1.0)
    assert bounded.summary()["trace_dropped_events"] == 3  # dropped new


def _flight(tmp_path, **kw):
    from repro.obs import FlightRecorder
    tel = Telemetry(clock=FakeClock(), trace_ring=True)
    kw.setdefault("slo_p95_s", 1.0)
    kw.setdefault("window", 8)
    kw.setdefault("min_steps", 4)
    fr = FlightRecorder(tel, dump_dir=str(tmp_path), **kw)
    assert tel.flight is fr  # self-registers for record_step feeding
    return tel, fr


def test_flight_recorder_healthy_run_never_dumps(tmp_path):
    tel, fr = _flight(tmp_path)
    for i in range(50):
        assert fr.observe_step(0.1, step_idx=i) is None
    assert fr.dumps == []
    assert not list(tmp_path.iterdir())
    assert tel.summary()["slo_dumps"] == 0


def test_flight_recorder_breach_dumps_once_and_latches(tmp_path):
    tel, fr = _flight(tmp_path)
    dumped = [fr.observe_step(5.0, step_idx=i) for i in range(20)]
    fired = [d for d in dumped if d]
    assert len(fired) == 1  # latched: a sustained breach is ONE dump
    assert dumped[fr.min_steps - 1] == fired[0]  # at the warmup boundary
    assert fr.dumps == fired
    trace = json.loads((tmp_path / "slo_dump_000_trace.json").read_text())
    assert any(e["name"] == "slo_breach"
               for e in trace["traceEvents"])
    [snap] = Registry.read_jsonl(str(tmp_path /
                                     "slo_dump_000_metrics.jsonl"))
    assert snap["meta"]["reason"] == "slo_p95_breach"
    assert snap["meta"]["slo_s"] == 1.0
    s = tel.summary()
    assert s["slo_dumps"] == 1
    assert s["slo_last_dump"].endswith("slo_dump_000")
    assert tel.metrics.value("repro_slo_dumps_total") == 1


def test_flight_recorder_min_steps_guard(tmp_path):
    _, fr = _flight(tmp_path, min_steps=6)
    for i in range(5):  # all breaching, but under the warmup floor
        assert fr.observe_step(9.0, step_idx=i) is None
    assert fr.observe_step(9.0, step_idx=5) is not None


def test_flight_recorder_rearms_after_recovery(tmp_path):
    _, fr = _flight(tmp_path, window=4, min_steps=4, rearm_ratio=0.5)
    assert [bool(fr.observe_step(9.0)) for _ in range(4)][-1]
    # recovery: window refills with fast steps; p95 drops under
    # rearm_ratio * slo -> re-armed, the NEXT breach dumps again (and
    # immediately re-latches)
    for _ in range(4):
        assert fr.observe_step(0.1) is None
    assert [bool(fr.observe_step(9.0)) for _ in range(4)] == \
        [True, False, False, False]
    assert len(fr.dumps) == 2
    assert fr.dumps[1].endswith("slo_dump_001")


def test_flight_recorder_rolling_p95_math(tmp_path):
    _, fr = _flight(tmp_path, window=100, min_steps=1, slo_p95_s=99.0)
    for i in range(1, 101):
        fr.observe_step(float(i))
    assert fr.rolling_p95() == 95.0  # index ceil(.95*100)-1 of 1..100
