"""`correct` on a whole run at a small size on the CPU: true for the
program as it is, false with the timed path broken underneath.

Each case drives the rest of a run (`bench.run.main` without its look for
a chip) on the test cell in `data/cells`: weights, engine, warm-up, the
open loop, the window and the comparison with the reference.
"""
import pathlib

import jax
import jax.numpy as jnp
import pytest

from bench import run

CELLS = pathlib.Path(__file__).resolve().parent / "data" / "cells"
SEED = 3_000_000_007


def _run(patch=None, seed=SEED):
    return run.main(["--workload", "tiny.chat", "--seed", str(seed),
                     "--seconds", "3", "--trace", "0"],
                    require_tpu=False, patch=patch, emit=False,
                    bench_json=CELLS / "BENCHMARK.json", root=CELLS)


def _wrap_step(eng, change, batch_change=lambda b: b):
    """Make every packed step the engine builds pass through `change`:
    (sampled tokens, cache in, cache out) -> (tokens, cache), with its
    batch through `batch_change` first."""
    build = eng.executor.build_unified

    def broken(kcfg):
        step = build(kcfg)

        def run_step(params, cache, batch):
            toks, new_cache = step(params, cache, batch_change(batch))
            return change(toks, cache, new_cache)

        return jax.jit(run_step)

    eng.executor.build_unified = broken


def token_altered(eng):
    vocab = eng.cfg.vocab_size
    _wrap_step(eng, lambda t, c, n: ((t + 1) % vocab, n))


def state_unchanged(eng):
    _wrap_step(eng, lambda t, c, n: (t, c))


def top_p_dropped(eng):
    """The sampler draws from the whole distribution at the temperature."""
    _wrap_step(eng, lambda t, c, n: (t, n),
               lambda b: dict(b, top_p=jnp.ones_like(b["top_p"])))


@pytest.fixture(scope="module")
def sound():
    return _run()


def test_sound_run_is_correct(sound):
    assert sound["correct"], sound["checks"]
    assert sound["checks"]["window_compiles"]["value"] == 0
    assert sound["checks"]["compared_tokens"]["value"] >= 20
    assert sound["checks"]["sampled_tokens"]["value"] >= 20
    assert sound["checks"]["nucleus_excess"]["value"] == 0.0
    assert set(sound["metrics"]) == {"ttft_p95_ms", "itl_p95_ms",
                                     "output_tok_s", "setup_s"}
    assert sound["attempted"] > 0 and sound["failed"] == 0


@pytest.mark.parametrize("fault", [token_altered, state_unchanged],
                         ids=lambda f: f.__name__)
def test_a_broken_step_is_not_correct(fault):
    res = _run(patch=fault)
    assert not res["correct"], res["checks"]
    assert res["checks"]["logit_gap"]["value"] > 0.5


def test_a_sampler_without_top_p_is_not_correct():
    res = _run(patch=top_p_dropped)
    assert not res["correct"], res["checks"]
    assert res["checks"]["logit_gap"]["value"] < 0.02  # greedy rows as before
    assert res["checks"]["nucleus_excess"]["value"] > 0.02


def test_result_line_keys(sound):
    assert list(sound)[:5] == ["correct", "attempted", "failed", "metrics",
                               "device"]
    assert list(sound)[-2] == "checks"  # last key of the printed line
    assert jnp.isfinite(sound["metrics"]["output_tok_s"]["value"])
