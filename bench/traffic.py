"""The one traffic generator: a mix file in `bench/traffic/<name>.json`
becomes a list of requests, each with the time it is due.

A mix file holds parameters only:

    arrival      {"process": "poisson", "rate_per_s": r}
                 {"process": "backlog"}  (every request due at t = 0)
    requests     how many requests the schedule holds
    block        requests per block (see below)
    prompt_len   a length distribution: {"dist": "lognormal", "median": m,
    output_len   "sigma": s, "min": lo, "max": hi} or {"dist": "uniform",
                 "min": lo, "max": hi}
    sampling     {"temperature": t, "top_p": p, "greedy_share": g}: a share
                 g of each block decodes greedily, the rest samples
    ramp_s       open loop: seconds of arrivals before the window opens
    ramp_done    backlog: finished requests that end the ramp

Work is the same for every seed.  The lengths of a block are the quantiles
(i + 0.5) / block of their distributions, as are the gaps between its
arrivals (exponential quantiles), and the greedy requests are a fixed count
per block.  Each block is shuffled by a generator with a fixed seed, so
every run offers the same sizes at the same times; the run's seed draws
the token ids and the sampling streams.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import statistics

import numpy as np

TRAFFIC_DIR = pathlib.Path(__file__).resolve().parent / "traffic"


@dataclasses.dataclass
class Arrival:
    due_s: float  # seconds after the schedule starts
    prompt: list[int]
    max_new_tokens: int
    temperature: float
    top_p: float
    stream: int  # sampling stream id: unique in the schedule, from the seed


def load_mix(name: str, directory: pathlib.Path = TRAFFIC_DIR) -> dict:
    path = directory / f"{name}.json"
    if not path.is_file():
        raise SystemExit(f"traffic mix {name!r}: no file {path}")
    return json.loads(path.read_text())


def _quantiles(dist: dict, n: int) -> np.ndarray:
    """Lengths at the quantiles (i + 0.5) / n of `dist`, ascending ints."""
    p = (np.arange(n) + 0.5) / n
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "uniform":
        x = lo + p * (hi - lo + 1)
        return np.clip(np.floor(x), lo, hi).astype(np.int64)
    if dist["dist"] == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(float(q)) for q in p])
        x = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
        return np.clip(np.rint(x), lo, hi).astype(np.int64)
    raise ValueError(f"unknown length distribution {dist['dist']!r}")


def _gaps(arrival: dict, n: int) -> np.ndarray:
    """Inter-arrival gaps of one block: exponential quantiles at the rate."""
    if arrival["process"] == "backlog":
        return np.zeros(n)
    p = (np.arange(n) + 0.5) / n
    return -np.log1p(-p) / float(arrival["rate_per_s"])


def schedule(mix: dict, seed: int, vocab: int) -> list[Arrival]:
    """The requests of one run, sorted by due time."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32])
    layout = np.random.default_rng(0)
    n, block = int(mix["requests"]), int(mix["block"])
    samp = mix.get("sampling", {})
    greedy_n = round(float(samp.get("greedy_share", 1.0)) * block)
    p_q = _quantiles(mix["prompt_len"], block)
    o_q = _quantiles(mix["output_len"], block)
    g_q = _gaps(mix["arrival"], block)
    greedy_q = np.arange(block) < greedy_n
    stream0 = int(rng.integers(0, 2 ** 30))
    out: list[Arrival] = []
    t = 0.0
    for b0 in range(0, n, block):
        m = min(block, n - b0)
        p_len = layout.permutation(p_q)[:m]
        o_len = layout.permutation(o_q)[:m]
        gaps = layout.permutation(g_q)[:m]
        greedy = layout.permutation(greedy_q)[:m]
        for i in range(m):
            t += float(gaps[i])
            prompt = rng.integers(1, vocab, size=int(p_len[i])).tolist()
            g = bool(greedy[i])
            out.append(Arrival(
                due_s=t, prompt=prompt, max_new_tokens=int(o_len[i]),
                temperature=0.0 if g else float(samp.get("temperature", 0.0)),
                top_p=1.0 if g else float(samp.get("top_p", 1.0)),
                stream=stream0 + b0 + i))
    return out
