"""The control comes out as not correct at the test cell's size.

The control is the plain reference with its linear layers and its KV
cache in float8 e4m3, the precision below the configuration's bfloat16
(`bench/control.py` reads it on the chip at the cells' own sizes).  At
each position of the same sequences, the token it puts first lies below
the exact reference's best by more than the test cell's `logit_gap`
limit, which sound runs of the program keep under (`test_correct.py`),
and `bench.check` judges it not correct.
"""
import json
import pathlib

import numpy as np
import pytest

from bench import check, traffic

CELLS = pathlib.Path(__file__).resolve().parent / "data" / "cells"
SPEC = json.loads((CELLS / "configs" / "tiny.json").read_text())
LIMITS = dict(check.load_limits("tiny.chat", CELLS / "limits"))
LIMITS.pop("nucleus_excess")  # greedy sequences only
LIMIT = LIMITS["logit_gap"]


@pytest.mark.parametrize("seed", [3_000_000_011, 12, 13])
def test_fp8_control_exceeds_the_limit(seed):
    rng = np.random.default_rng(seed)
    vocab = SPEC["vocab_size"]
    picked = [(traffic.Arrival(0.0, rng.integers(1, vocab, 64).tolist(),
                               448, 0.0, 1.0, i),
               rng.integers(1, vocab, 448).tolist()) for i in range(4)]
    r = check.readings(SPEC, seed, picked, "fp8")
    assert not check.within(LIMIT, float(r["control_gap"].max()))
    assert check.within(LIMIT, 0.0)
    assert not check.all_within(check.numbers(r, LIMITS, gap="control_gap"))
