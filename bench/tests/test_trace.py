"""The reduction from trace events to busy time, op time and idle gaps."""
import pathlib

import pytest

from bench import trace

DATA = pathlib.Path(__file__).resolve().parent / "data"
MS = 1_000_000  # ns


def test_reduce_events_by_hand():
    spans = [("bench.window", 0, 100 * MS),
             ("bench.stream_next", 10 * MS, 40 * MS),
             ("bench.wait_for_arrival", 60 * MS, 90 * MS)]
    dev = {"/device:TPU:0": [
        ("fusion.1", 0, 10 * MS),
        ("paged_decode.3", 5 * MS, 12 * MS),   # overlaps the fusion
        ("fusion.2", 40 * MS, 60 * MS),
        ("copy.7", 95 * MS, 120 * MS),         # runs past the window
    ]}
    r = trace.reduce_events(dev, spans)
    assert r["window_s"] == pytest.approx(0.1)
    # busy: [0, 12] + [40, 60] + [95, 100] = 37 ms
    assert r["busy_s"] == pytest.approx(0.037)
    assert r["ops_s"]["fusion"] == pytest.approx(0.030)
    assert r["ops_s"]["paged_decode"] == pytest.approx(0.007)
    assert r["ops_s"]["copy"] == pytest.approx(0.005)
    # idle: [12, 40] (stream_next), [60, 95] (wait_for_arrival)
    assert r["gaps"] == [["bench.wait_for_arrival", pytest.approx(0.035)],
                         ["bench.stream_next", pytest.approx(0.028)]]


def test_chips_are_averaged():
    spans = [("bench.window", 0, 10 * MS)]
    dev = {"/device:TPU:0": [("a", 0, 10 * MS)],
           "/device:TPU:1": [("a", 0, 5 * MS)]}
    r = trace.reduce_events(dev, spans)
    assert r["busy_s"] == pytest.approx(0.0075) and r["chips"] == 2


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce_events({"/device:TPU:0": []}, [])


def test_op_names():
    assert trace.op_name("fusion.123") == "fusion"
    assert trace.op_name("paged_prefill_qblock") == "paged_prefill_qblock"


def test_hlo_text_names():
    assert trace.op_name("%paged_decode.8 = bf16[128,3,3,128] custom-call("
                         "s32[128,16] %x), custom_call_target=\"tpu\"") \
        == "paged_decode"
    assert trace.op_name("%while.27 = (s32[]) while(%t)") == "while"


def test_loops_are_not_ops():
    spans = [("bench.window", 0, 10 * MS)]
    dev = {"/device:TPU:0": [("%while.1 = () while()", 0, 10 * MS),
                             ("%fusion.2 = f32[] fusion()", 1 * MS, 3 * MS)]}
    r = trace.reduce_events(dev, spans)
    assert r["ops_s"] == {"fusion": pytest.approx(0.002)}
    assert r["busy_s"] == pytest.approx(0.010)


def test_recorded_chip_trace():
    """Three engine steps of a 4-layer SmolLM-width model on one TPU v5e,
    each step in `bench.stream_next`, each followed by a 5 ms
    `bench.wait_for_arrival` (recorded with the profiler on the chip)."""
    r = trace.reduce_trace(str(DATA / "trace"))
    assert r["chips"] == 1
    assert 0.0 < r["busy_s"] < r["window_s"] < 0.1
    assert r["ops_s"]["paged_decode"] > 0  # the Pallas kernel, by name
    assert sum(r["ops_s"].values()) > 0.5 * r["busy_s"]
    names = {n for n, _ in r["gaps"]}
    assert names <= {"bench.stream_next", "bench.wait_for_arrival",
                     "bench.submit", "host.other"}
    # the three sleeps are the longest idle gaps
    assert [n for n, _ in r["gaps"][:3]] == ["bench.wait_for_arrival"] * 3
    assert all(t >= 0.005 for _, t in r["gaps"][:3])
