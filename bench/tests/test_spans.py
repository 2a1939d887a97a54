"""The reduction of the engine's phase spans and request stamps, and the
readers of the metrics built on it."""
import importlib.util
import pathlib
from types import SimpleNamespace

import pytest

from bench import spans, trace

DATA = pathlib.Path(__file__).resolve().parent / "data"
METRICS = pathlib.Path(__file__).resolve().parents[1] / "metrics"
MS = 1_000_000  # ns


def reader(name):
    spec = importlib.util.spec_from_file_location(
        f"reader_{name}", METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def sp(name, s, e, step=None):
    return (name, s * MS, e * MS, step)


# Two yields of the stream in a 100 ms window.  Step 0 was scheduled
# before the window and step 2 finishes after it: only step 1 lies
# wholly inside.  The draft span nests in the schedule span.
SPANS = [
    sp("bench.window", 0, 100),
    sp("repro.schedule", -10, -8, 0), sp("repro.pack", -8, -6, 0),
    sp("repro.dispatch", -6, -5, 0),
    sp("bench.stream_next", 5, 60),
    sp("repro.schedule", 5, 10, 1), sp("repro.draft", 7, 9, 1),
    sp("repro.pack", 10, 15, 1), sp("repro.dispatch", 15, 18, 1),
    sp("repro.wait_tokens", 18, 40, 0), sp("repro.consume", 40, 42, 0),
    sp("repro.finish", 42, 45, 0),
    sp("bench.submit", 60, 62),
    sp("bench.stream_next", 62, 99),
    sp("repro.wait_tokens", 62, 80, 1), sp("repro.consume", 80, 81, 1),
    sp("repro.finish", 81, 83, 1),
    sp("repro.schedule", 90, 95, 2), sp("repro.finish", 101, 102, 2),
]
# chip 0 busy [0, 6], [19, 41], [63, 79], [95, 105): the last op runs
# past the window's close
OPS = [(0, 6 * MS), (19 * MS, 41 * MS), (63 * MS, 79 * MS),
       (95 * MS, 105 * MS)]


def test_reduce_events_by_hand():
    r = spans.reduce_events(OPS, SPANS)
    assert r["window_s"] == pytest.approx(0.1)
    # idle [6, 19], [41, 63], [79, 95]
    assert r["idle_s"] == pytest.approx(0.051)
    want = {"repro.schedule": 7, "repro.draft": 2, "repro.pack": 5,
            "repro.dispatch": 3, "repro.wait_tokens": 3,
            "repro.consume": 2, "repro.finish": 5,
            "engine.other": 22, "host.other": 2}
    assert r["idle_by_span"] == pytest.approx(
        {k: v * 1e-3 for k, v in want.items()})
    assert sum(r["idle_by_span"].values()) == pytest.approx(r["idle_s"])
    assert r["gaps"] == [
        ["engine.other", pytest.approx(0.022), pytest.approx(0.041)],
        ["engine.other", pytest.approx(0.016), pytest.approx(0.079)],
        ["repro.pack", pytest.approx(0.013), pytest.approx(0.006)]]


def test_self_time_less_children_over_whole_steps():
    r = spans.reduce_events(OPS, SPANS)
    assert r["steps"] == 1  # steps 0 and 2 cross the window's edges
    # the schedule span's 5 ms less the 2 ms draft nested in it
    assert r["self_ms"] == pytest.approx({
        "repro.schedule": 3.0, "repro.draft": 2.0, "repro.pack": 5.0,
        "repro.dispatch": 3.0, "repro.wait_tokens": 18.0,
        "repro.consume": 1.0, "repro.finish": 2.0})
    # everything but the wait for tokens: 3 + 2 + 5 + 3 + 1 + 2
    assert r["host_step_ms"] == [pytest.approx(16.0)]


def test_idle_outside_the_engine():
    """With no engine spans every idle stretch is the harness's or the
    engine's untraced code, and the parts still sum to the idle time."""
    bare = [s for s in SPANS if not s[0].startswith("repro.")]
    r = spans.reduce_events(OPS, bare)
    assert set(r["idle_by_span"]) == {"engine.other", "host.other"}
    # [60, 62] between the two yields is the harness's
    assert r["idle_by_span"]["host.other"] == pytest.approx(0.002)
    assert sum(r["idle_by_span"].values()) == pytest.approx(0.051)
    assert r["steps"] == 0 and r["host_step_ms"] == []


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        spans.reduce_events(OPS, [s for s in SPANS if s[0] != "bench.window"])


def test_recorded_chip_trace_agrees_with_busy_time():
    """On the recorded chip trace (a program without engine spans) the
    idle time is the window less `bench.trace`'s busy time."""
    r = spans.reduce_trace(str(DATA / "trace"))
    t = trace.reduce_trace(str(DATA / "trace"))
    assert r["window_s"] == pytest.approx(t["window_s"])
    assert r["idle_s"] == pytest.approx(t["window_s"] - t["busy_s"])
    assert sum(r["idle_by_span"].values()) == pytest.approx(r["idle_s"])


def test_recorded_chip_trace_with_engine_spans():
    """A reduced SmolLM serving three requests through `Engine.stream()`
    on one TPU v5e, each yield in `bench.stream_next` (recorded with the
    profiler on the chip): the engine's spans come with their step ids,
    every idle stretch is put down, and the longest gaps are the ones
    `bench.trace` finds."""
    r = spans.reduce_trace(str(DATA / "trace_spans"))
    t = trace.reduce_trace(str(DATA / "trace_spans"))
    assert r["idle_s"] == pytest.approx(t["window_s"] - t["busy_s"])
    assert sum(r["idle_by_span"].values()) == pytest.approx(r["idle_s"])
    assert r["steps"] >= 4 and len(r["host_step_ms"]) == r["steps"]
    assert {"repro.schedule", "repro.pack", "repro.dispatch",
            "repro.wait_tokens", "repro.consume",
            "repro.finish"} <= set(r["self_ms"])
    assert [g[1] for g in r["gaps"][:3]] == pytest.approx(
        [g[1] for g in t["gaps"][:3]])
    assert all(g[0].startswith("repro.") for g in r["gaps"][:3])


def test_queue_wait_ms():
    def req(sub, adm):
        return SimpleNamespace(submitted_at=sub, admitted_at=adm)
    reqs = [(1.0, req(1.0, 1.5)),    # due and admitted in the window
            (2.0, req(2.1, None)),   # still waiting at the close (3.0)
            (2.5, req(2.5, 3.2)),    # admitted after the close
            (0.5, req(0.5, 1.2)),    # due before the window
            (1.0, SimpleNamespace())]  # a program without stamps
    assert spans.queue_wait_ms(reqs, 1.0, 3.0) == pytest.approx(
        [500.0, 900.0, 500.0])


def test_readers():
    rec = {"spans": spans.reduce_events(OPS, SPANS),
           "queue_wait_ms": [float(x) for x in range(1, 101)]}
    assert reader("queue_wait_p95_ms")(rec) == pytest.approx(95.05)
    assert reader("host_step_ms")(rec) == pytest.approx(16.0)
    # schedule 7 + draft 2 + pack 5 + dispatch 3 + consume 2 + finish 5
    # ms of idle in a 100 ms window
    assert reader("idle_host_pct")(rec) == pytest.approx(24.0)


@pytest.mark.parametrize("name", ["queue_wait_p95_ms", "host_step_ms",
                                  "idle_host_pct"])
def test_readers_find_nothing_in_a_record_without_spans(name):
    """A record from a harness or program that lacks the spans and
    stamps reads as no value, not as an error."""
    rec = {"trace": None, "window_s": 50.0, "queue_wait_ms": []}
    assert reader(name)(rec) is None


def test_phases_reads_a_whole_run(monkeypatch, tmp_path):
    """`bench/phases.py` on the tiny cell on the CPU, the CPU's XLA
    threads standing in for the chip's op line: every number is there,
    and the idle parts sum to the idle time (whether the run is `correct`
    is `test_correct.py`'s to judge)."""
    from jax.profiler import ProfileData

    from bench import phases

    def events(path):
        ops, host = [], []
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                for ev in line.events:
                    iv = (int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                    if line.name.startswith("tf_XLA"):
                        ops.append(iv)
                    elif ev.name.startswith(("repro.", "bench.")):
                        host.append((ev.name, *iv, dict(ev.stats).get("step")))
        return ops, host

    def read_planes(path):
        ops, host = events(path)
        return ({"/device:TPU:0": [("op", s, e) for s, e in ops]},
                [h[:3] for h in host if h[0].startswith("bench.")])

    from bench import run
    monkeypatch.setattr(trace, "read_planes", read_planes)
    monkeypatch.setattr(spans, "read_spans", events)
    # phases hooks these in place: restore them after the test
    monkeypatch.setattr(trace, "reduce_trace", trace.reduce_trace)
    monkeypatch.setattr(run, "end_to_end", run.end_to_end)
    monkeypatch.setattr(run, "_snapshot", run._snapshot)
    cells = DATA / "cells"
    out = phases.main(["--workload", "tiny.chat", "--seed", "3000000007",
                       "--seconds", "3", "--out", str(tmp_path / "p.jsonl")],
                      require_tpu=False, bench_json=cells / "BENCHMARK.json",
                      root=cells)
    assert all(v is not None for v in out["new_metrics"].values())
    assert out["spans"]["steps"] > 0 and out["spans_per_step"] > 0
    assert abs(out["idle_parts_less_idle_pct"]) < 1e-6
    assert out["waits_over_ttft"] == 0 and out["preemptions"] == 0
    assert (tmp_path / "p.jsonl").read_text().count("\n") == 1
