"""Cells, configurations, traffic mixes, limits and metric readers are
found by their names, so a later cell needs new files and no edit."""
import json
import pathlib
import shutil

from bench import check, run, traffic

CELLS = pathlib.Path(__file__).resolve().parent / "data" / "cells"
REPO = pathlib.Path(__file__).resolve().parents[2]


def test_a_new_cell_is_found_by_name(tmp_path: pathlib.Path):
    root = tmp_path / "bench"
    shutil.copytree(CELLS, root)
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    # the later PR: new files, and one appended entry
    (root / "configs" / "tiny_wide.json").write_text(json.dumps(
        dict(json.loads((root / "configs" / "tiny.json").read_text()),
             intermediate_size=384)))
    (root / "traffic" / "burst.json").write_text(json.dumps(
        dict(traffic.load_mix("tiny_chat", root / "traffic"),
             arrival={"process": "poisson", "rate_per_s": 80.0})))
    (root / "limits" / "tiny_wide.burst.json").write_text(
        (root / "limits" / "tiny.chat.json").read_text())
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny_wide.burst",
                               "config": "tiny_wide", "traffic": "burst",
                               "chips": 1, "why": "test"})
    (tmp_path / "B.json").write_text(json.dumps(bench))
    _, cell, spec, mix = run.load_cell("tiny_wide.burst",
                                       tmp_path / "B.json", root)
    assert cell["config"] == "tiny_wide"
    assert spec["intermediate_size"] == 384
    assert mix["arrival"]["rate_per_s"] == 80.0
    assert check.load_limits("tiny_wide.burst", root / "limits")
    for p, data in before.items():
        assert p.read_bytes() == data  # nothing there was edited


def test_every_benchmark_cell_has_its_files():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        _, _, spec, _ = run.load_cell(w["name"], REPO / "BENCHMARK.json",
                                      run.BENCH)
        assert spec["reference"]
        assert check.load_limits(w["name"])
    for m in bench["per_layer"]:
        assert callable(run._reader(m["name"]))
    files = {c["file"] for c in bench["configs"]}
    assert all((REPO / f).is_file() for f in files)


def test_readers_stay_silent_without_data():
    rec = {"dims": {}, "peaks": None, "window_s": 0.0, "trace": None,
           "counters": {"prefilled_tokens": 0, "launched_token_slots": 0},
           "prompts": [], "decode_ctx": [], "sampled_rows": 0}
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        assert run._reader(m["name"])(rec) is None, m["name"]


def test_a_backlog_window_counts_the_requests_it_worked_on():
    from types import SimpleNamespace as NS

    def req(due, times, n, generated=None, done=None):
        generated = len(times) if generated is None else generated
        done = generated == n if done is None else done
        return {"due": due, "times": times, "arrival": NS(max_new_tokens=n),
                "req": NS(done=done, num_generated=generated)}

    loop = NS(sys=NS(is_finished=lambda r: r.done), reqs={
        0: req(0.0, [1.0, 2.0], 2),         # finished before the window
        1: req(0.0, [1.0, 11.0, 12.0], 3),  # due at 0, served in the window
        2: req(0.0, [], 4),                 # due at 0, still waiting
        3: req(12.0, [13.0], 1),            # due in the window
        4: req(5.0, [6.0, 19.0], 3, 3),     # finished, last token unread
        5: req(15.0, [16.0], 4, 1, True),   # finished short by the engine
    })
    e2e = run.end_to_end(loop, 10.0, 20.0)
    assert (e2e["due"], e2e["attempted"], e2e["failed"]) == (2, 4, 1)
    assert e2e["tokens"] == 5
    assert e2e["itl_ms"] == [10000.0, 1000.0, 13000.0]
    assert e2e["ttft_ms"] == [1000.0, 1000.0]
