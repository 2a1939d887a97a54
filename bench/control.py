#!/usr/bin/env python3
"""Readings for the limits of `correct`: the program, its control, and a
sampler fault.

    python bench/control.py --workload <cell> --seeds 11,12,13 --seconds 50

For each seed, in one process: one run of the cell as `bench/run.py`
makes it (a `--seconds` window at the cell's own load), then the same
samples of served requests through the reference, read three ways and
each judged by `bench.check` against the cell's limits:

    program  the tokens the system served
    control  at each greedy position, the first choice of the reference
             in the precision below the configuration's bfloat16 (fp8,
             see `bench/configs/dense_gqa.py`) in the program's place
    fault    at each sampled position, a token drawn from the reference's
             whole distribution at the request's temperature: a sampler
             whose top-p filter is left out

Prints one JSON line per seed with each one's compared numbers and
`correct`, the share of greedy positions whose token is not the
reference's first choice, and the mean `mass_above` of the sampled
positions.  A limit lies between the largest program
reading over a dozen seeds or more and the smallest control or fault
reading.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

CHECKOUT = pathlib.Path(__file__).resolve().parents[1]
CONTROL = "fp8"


def judged(spec: dict, seed: int, served: list, limits: dict) -> dict:
    """The program's, the control's and the fault's compared numbers on
    the run's samples, each with its `correct`."""
    from bench import check
    pairs = check.picked(served, seed, limits)
    r = check.readings(spec, seed, pairs, CONTROL, draw=True)
    out = {}
    ways = [("program", {}), ("control", {"gap": "control_gap"})]
    if "nucleus_excess" in limits:
        ways.append(("fault", {"mass": "drawn_mass_above"}))
    for name, kw in ways:
        nums = check.numbers(r, limits, **kw)
        out[name] = {"correct": check.all_within(nums),
                     **{k: v["value"] for k, v in nums.items()}}
    g = r["greedy"]
    out["program"]["flip_share"] = float((r["gap"][g] > 0).mean())
    out["control"]["flip_share"] = float((r["control_gap"][g] > 0).mean())
    if (~g).any():  # a sound sampler puts this near top_p / 2
        out["program"]["mass_above_mean"] = float(r["mass_above"][~g].mean())
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=50.0)
    args = ap.parse_args()
    sys.path.insert(0, str(CHECKOUT))
    from bench import check, run
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run.main(["--workload", args.workload, "--seed", str(seed),
                        "--seconds", str(args.seconds), "--trace", "0"],
                       emit=False)
        _, _, spec, _ = run.load_cell(args.workload,
                                      CHECKOUT / "BENCHMARK.json", run.BENCH)
        limits = check.load_limits(args.workload)
        out = {"seed": seed, "correct": res["correct"],
               "metrics": {k: v["value"] for k, v in res["metrics"].items()},
               **judged(spec, seed, res["served"], limits)}
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
