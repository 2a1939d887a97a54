#!/usr/bin/env python3
"""Find the highest arrival rate an open-loop cell sustains, on the chip.

    python bench/sweep.py --workload <cell> --seed <n> --rates 3,4,5 \
        [--ramp 10] [--seconds 30]

One process builds the cell's engine once, warms it up, and then offers
the cell's traffic at each rate in turn: `--ramp` seconds of arrivals,
then a `--seconds` window, then a drain with no new arrivals.  For each
rate it prints one JSON line: TTFT and inter-token percentiles, output
tokens/s beside the tokens/s the requests due in the window ask for, and
the backlog (requests waiting) at the start and end of the window.  A
rate is sustained when the backlog does not grow through the window and
the output keeps up with what is offered; take windows of two request
lifetimes or more.  The benchmark's own runs never call this; its result is the
rate written into the cell's traffic file.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

CHECKOUT = pathlib.Path(__file__).resolve().parents[1]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--ramp", type=float, default=10.0)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--drain", type=float, default=90.0)
    args = ap.parse_args()

    import os
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CHECKOUT / ".jax_cache")
    sys.path.insert(0, str(CHECKOUT / "src"))
    sys.path.insert(0, str(CHECKOUT))
    from bench import run
    bench, cell, spec, mix = run.load_cell(
        args.workload, CHECKOUT / "BENCHMARK.json", run.BENCH)
    import jax
    _, device = run._device_info(jax)
    print(f"device: {device}", file=sys.stderr)
    if device["platform"] != "tpu":
        raise SystemExit("the sweep measures on a TPU only")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench import system, traffic
    system.enable_compile_cache()
    eng = run.build_engine(spec, args.seed)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        m = dict(mix, arrival=dict(mix["arrival"], rate_per_s=rate))
        arrivals = traffic.schedule(m, args.seed + i, spec["vocab_size"])
        loop = run.OpenLoop(eng, arrivals, system)
        loop.run(lambda now: now >= loop.t0 + args.ramp)
        wait0 = len(eng.sched.waiting)
        t_w0 = time.perf_counter()
        loop.run(lambda now: now >= t_w0 + args.seconds)
        t_w1 = time.perf_counter()
        wait1 = len(eng.sched.waiting)
        running = len(eng.sched.running)
        e2e = run.end_to_end(loop, t_w0, t_w1)
        half = t_w0 + args.seconds / 2
        late = [r for r in loop.reqs.values() if half <= r["due"] < t_w1]
        early = [r for r in loop.reqs.values() if t_w0 <= r["due"] < half]

        def ttft95(rs):
            v = [((r["times"][0] if r["times"] else t_w1) - r["due"]) * 1e3
                 for r in rs]
            return run.percentile(v, 95) if v else None

        loop.pending.clear()
        loop.run(lambda now: not eng.sched.has_work and loop.it is None
                 or now >= t_w1 + args.drain)
        print(json.dumps({
            "rate_per_s": rate, "due": e2e["due"],
            "ttft_p50_ms": run.percentile(e2e["ttft_ms"], 50),
            "ttft_p95_ms": run.percentile(e2e["ttft_ms"], 95),
            "ttft_p95_first_half_ms": ttft95(early),
            "ttft_p95_second_half_ms": ttft95(late),
            "itl_p50_ms": run.percentile(e2e["itl_ms"], 50),
            "itl_p95_ms": run.percentile(e2e["itl_ms"], 95),
            "output_tok_s": e2e["tokens"] / (t_w1 - t_w0),
            "offered_tok_s": sum(
                r["arrival"].max_new_tokens for r in loop.reqs.values()
                if t_w0 <= r["due"] < t_w1) / (t_w1 - t_w0),
            "waiting_start": wait0, "waiting_end": wait1,
            "running_end": running,
            "drained": not eng.sched.has_work}), flush=True)
        loop.close()


if __name__ == "__main__":
    main()
