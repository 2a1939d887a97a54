#!/usr/bin/env python3
"""One traced run of a cell, read through the engine's own records.

    python bench/phases.py --workload <cell> --seed <n> --seconds 50 \\
        [--out chiprun_out/phases.jsonl]

Runs the cell as `bench/run.py --trace 1` does, in one process, and also
reduces from the same trace and window what the program records of
itself (`bench.spans`): each engine phase's self time per step, the
chip's idle time put down to the phase that held it, and the longest
idle gaps; and, from the scheduler's stamps on each request, the
admission wait of the requests due in the window.  From those it reads
`queue_wait_p95_ms`, `host_step_ms` and `idle_host_pct` with their
readers in `bench/metrics/`.  It also prints the traced window's own
end-to-end numbers (to set beside `--trace 0` runs: the cost of the
profiler) and the cost of one phase span with no profiler recording.

Prints one JSON line (and appends it to `--out`); logs the window's
preemptions and idle seconds per phase on standard error.  The
benchmark's own runs never run this: it hooks `bench/run.py`'s trace
reduction, window snapshot and end-to-end count in this process.
"""
from __future__ import annotations

import argparse
import json
import logging
import pathlib
import statistics
import sys
import time

CHECKOUT = pathlib.Path(__file__).resolve().parents[1]
READERS = ("queue_wait_p95_ms", "host_step_ms", "idle_host_pct")

log = logging.getLogger("bench")


def span_ns(n: int = 200_000) -> float:
    """Nanoseconds one engine phase span costs with no profiler on."""
    from repro.serving.spans import Span
    t = time.perf_counter()
    for i in range(n):
        with Span("probe", i):
            pass
    return (time.perf_counter() - t) / n * 1e9


def hook(run, spans, trace) -> dict:
    """Make `run.main` leave what this script reads in the returned
    dict: the span reduction of its trace, the window's admission waits
    beside each request's TTFT, and the scheduler's preemption count at
    the window's two snapshots."""
    got: dict = {"preemptions": []}
    reduce_trace, end_to_end, snapshot = (trace.reduce_trace,
                                          run.end_to_end, run._snapshot)

    def reduce_both(log_dir, top=10):
        got["spans"] = spans.reduce_trace(log_dir)
        return reduce_trace(log_dir, top)

    def end_to_end_and_waits(loop, t_w0, t_w1):
        e2e = end_to_end(loop, t_w0, t_w1)
        got["e2e"] = e2e
        due = [(r["due"], r["req"]) for r in loop.reqs.values()]
        got["queue_wait_ms"] = spans.queue_wait_ms(due, t_w0, t_w1)
        # the TTFT sample, request by request, against each wait
        over = 0
        for r in loop.reqs.values():
            wait = spans.queue_wait_ms([(r["due"], r["req"])], t_w0, t_w1)
            if wait:
                first = r["times"][0] if r["times"] else t_w1
                over += wait[0] > (min(first, t_w1) - r["due"]) * 1e3
        got["waits_over_ttft"] = over
        return e2e

    def snapshot_and_preemptions(eng):
        got["preemptions"].append(eng.sched.preemptions)
        return snapshot(eng)

    trace.reduce_trace = reduce_both
    run.end_to_end = end_to_end_and_waits
    run._snapshot = snapshot_and_preemptions
    return got


def summary(res: dict, got: dict, seconds: float, cost_ns: float,
            readers) -> dict:
    from bench import run
    e2e, sp = got["e2e"], got["spans"]
    record = {"spans": sp, "queue_wait_ms": got["queue_wait_ms"]}
    waits = got["queue_wait_ms"] or [0.0]
    per_step = sp["spans"] / max(sp["steps"], 1)
    step_ms = 1e3 * sp["window_s"] / max(sp["steps"], 1)
    return {
        "correct": res["correct"], "device": res["device"],
        "metrics": {k: v["value"] for k, v in res["metrics"].items()},
        "new_metrics": {name: readers(name)(record) for name in READERS},
        "traced_e2e": {
            "ttft_p95_ms": run.percentile(e2e["ttft_ms"], 95),
            "itl_p95_ms": run.percentile(e2e["itl_ms"], 95),
            "output_tok_s": e2e["tokens"] / seconds},
        "queue_wait_ms": {"n": len(got["queue_wait_ms"]),
                          "p50": run.percentile(waits, 50),
                          "p95": run.percentile(waits, 95),
                          "max": max(waits)},
        "waits_over_ttft": got["waits_over_ttft"],
        "preemptions": got["preemptions"][-1] - got["preemptions"][0],
        "idle_pct": 100.0 * sp["idle_s"] / sp["window_s"],
        "idle_parts_less_idle_pct": 100.0 * (
            sum(sp["idle_by_span"].values()) - sp["idle_s"])
        / sp["window_s"],
        "span_ns_profiler_off": cost_ns,
        "spans_per_step": per_step,
        "step_ms": step_ms,
        "span_cost_pct_of_step": 100.0 * cost_ns * 1e-6 * per_step
        / step_ms,
        "spans": {k: sp[k] for k in ("window_s", "idle_s", "idle_by_span",
                                     "steps", "self_ms", "gaps")},
        "host_step_ms_quartiles": statistics.quantiles(
            sp["host_step_ms"], n=4) if len(sp["host_step_ms"]) > 1
        else sp["host_step_ms"],
    }


def main(argv=None, **run_kw) -> dict:
    """`run_kw` go to `bench.run.main` (the tests' small CPU cell)."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", type=pathlib.Path)
    args = ap.parse_args(argv)
    for p in (CHECKOUT / "src", CHECKOUT):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    from bench import run, spans, trace
    got = hook(run, spans, trace)
    res = run.main(["--workload", args.workload, "--seed", str(args.seed),
                    "--seconds", str(args.seconds), "--trace", "1"],
                   emit=False, **run_kw)
    cost_ns = span_ns()  # after run.main has set up JAX as a run does
    out = {"workload": args.workload, "seed": args.seed,
           **summary(res, got, res["device"].get("window_s")
                     or args.seconds, cost_ns, run._reader)}
    sp = out["spans"]
    log.info("window: %d preemptions; chip 0 idle %.3f s of %.3f s: %s",
             out["preemptions"], sp["idle_s"], sp["window_s"],
             ", ".join(f"{k} {v:.4f} s" for k, v in sorted(
                 sp["idle_by_span"].items(), key=lambda kv: -kv[1])))
    line = json.dumps(out)
    print(line, flush=True)
    if args.out:
        args.out.parent.mkdir(parents=True, exist_ok=True)
        with args.out.open("a") as f:
            f.write(line + "\n")
    return out


if __name__ == "__main__":
    main()
