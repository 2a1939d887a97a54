"""The comparison that decides `correct`.

After the window has closed and the program's state is freed, two samples
of the requests the window finished, each drawn from the seed with the
longest request always in it, are run through the plain reference of the
configuration, layer by layer, over each prompt followed by the tokens
the system served:

- greedy requests: at every served position, the gap by which the served
  token's logit lies below the reference's best (`logit_gap`, the widest).
  Greedy decoding at the configuration's precision keeps it near
  rounding; a wrong page, a stale cache, a mask or a token altered where
  it is produced opens it.
- sampled requests: at every served position, the reference's probability
  (at the request's temperature) of the tokens whose logit lies above the
  served one, less the request's top_p (`nucleus_excess`, the largest,
  and 0 where every token lies inside).  Top-p sampling keeps it below 0:
  it draws from the smallest set of tokens whose probability reaches
  top_p.  A sampler that leaves the filter out or keeps too much, or a
  token altered where it is produced, puts tokens outside.

`bench/limits/<workload>.json` holds the limit of each compared number,
the readings it was set from, and the sizes of the two samples
(`"sample"`: served tokens each gathers at least; null for every request
of its kind).  A cell with no `nucleus_excess` limit compares no sampled
requests.
"""
from __future__ import annotations

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights
from bench.configs import reference

LIMITS_DIR = pathlib.Path(__file__).resolve().parent / "limits"
MIN_REQUESTS = 4  # requests each sample holds, where the window has them


def load_limits(workload: str, directory: pathlib.Path = LIMITS_DIR) -> dict:
    path = directory / f"{workload}.json"
    if not path.is_file():
        raise SystemExit(f"workload {workload!r}: no limits file {path}")
    return json.loads(path.read_text())


def sample(items: list, seed: int, min_tokens: int | None,
           salt: int) -> list:
    """(arrival, tokens) pairs: the one with the most served tokens, then
    others in an order drawn from the seed, until the sample holds
    `min_tokens` served tokens and MIN_REQUESTS requests; every pair where
    `min_tokens` is None."""
    if not items:
        return []
    items = sorted(items, key=lambda x: (-len(x[1]), -len(x[0].prompt),
                                         x[0].stream))
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, salt])
    rest = [items[i + 1] for i in rng.permutation(len(items) - 1)]
    out = [items[0]]
    for item in rest:
        n = sum(len(t) for _, t in out)
        if min_tokens is not None and n >= min_tokens and \
                len(out) >= MIN_REQUESTS:
            break
        out.append(item)
    return out


def picked(served: list, seed: int, limits: dict) -> list:
    """The greedy sample, then the sampled one (where the cell compares
    sampled requests)."""
    size = limits["sample"]
    greedy = [(a, t) for a, t in served if a.temperature <= 0 and t]
    out = sample(greedy, seed, size["greedy_tokens"], 11)
    if "nucleus_excess" in limits:
        rest = [(a, t) for a, t in served if a.temperature > 0 and t]
        out += sample(rest, seed, size["sampled_tokens"], 12)
    return out


def readings(spec: dict, seed: int, pairs: list, control: str | None = None,
             draw: bool = False) -> dict[str, np.ndarray]:
    """Per served position of the (arrival, tokens) pairs, the reference's
    readings (`head_readings` of the configuration's reference), with
    "greedy" and "top_p" of the request each position belongs to."""
    ref = reference(spec)
    seqs = [np.asarray(list(a.prompt) + list(t), np.int32) for a, t in pairs]
    matmuls = ("exact",) + ((control,) if control else ())
    hidden, glob = ref.final_hidden(spec, seed, seqs, matmuls)
    eps = float(spec["rms_norm_eps"])
    key = jax.random.fold_in(weights.seed_key(seed), 13)
    out: dict[str, list] = {}
    for i, ((a, _), seq) in enumerate(zip(pairs, seqs)):
        h = hidden[0][i]
        nxt = np.zeros((h.shape[0],), np.int32)
        nxt[: len(seq) - 1] = seq[1:]
        r = ref.head_readings(eps, control, draw, glob, h, hidden[-1][i],
                              jnp.asarray(nxt), jnp.float32(a.temperature),
                              jax.random.fold_in(key, i))
        lo, hi = len(a.prompt) - 1, len(seq) - 1  # the served positions
        for k, v in r.items():
            out.setdefault(k, []).append(np.asarray(v)[lo:hi])
        out.setdefault("greedy", []).append(
            np.full(hi - lo, a.temperature <= 0))
        out.setdefault("top_p", []).append(np.full(hi - lo, a.top_p))
    return {k: np.concatenate(v) for k, v in out.items()}


def numbers(r: dict | None, limits: dict, gap: str = "gap",
            mass: str = "mass_above") -> dict:
    """The compared numbers of one run, each beside its limit.  `gap` and
    `mass` name the readings that stand for the served tokens (a control
    or a fault names its own)."""
    greedy = r["greedy"] if r else np.zeros(0, bool)
    checks = {
        "compared_tokens": {"value": int(greedy.sum()),
                            "limit": limits["compared_tokens"]},
        "logit_gap": {"value": float(r[gap][greedy].max())
                      if greedy.any() else None,
                      "limit": limits["logit_gap"]},
    }
    if "nucleus_excess" in limits:
        s = ~greedy
        excess = r[mass][s] - r["top_p"][s] if s.any() else None
        checks["sampled_tokens"] = {"value": int(s.sum()),
                                    "limit": limits["sampled_tokens"]}
        checks["nucleus_excess"] = {
            "value": None if excess is None
            else max(0.0, float(excess.max())),
            "limit": limits["nucleus_excess"]}
    return checks


def compare(spec: dict, seed: int, served: list, limits: dict) -> dict:
    """The compared numbers of one run's served requests."""
    pairs = picked(served, seed, limits)
    return numbers(readings(spec, seed, pairs) if pairs else None, limits)


def within(limit, value) -> bool:
    """A limit is a number (the most allowed) or a string `<= x` / `>= x`."""
    if value is None:
        return False
    if isinstance(limit, str):
        op, x = limit.split()
        return value <= float(x) if op == "<=" else value >= float(x)
    return value <= limit


def all_within(checks: dict) -> bool:
    return all(within(c["limit"], c["value"]) for c in checks.values())
