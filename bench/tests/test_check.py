"""The readings behind `correct`, by hand."""
import jax
import jax.numpy as jnp
import numpy as np

from bench import check, traffic
from bench.configs import dense_gqa

EPS = 1e-5


def _head_case(seed=0, vocab=6, d=4):
    rng = np.random.default_rng(seed)
    glob = {"embed": jnp.asarray(rng.normal(size=(vocab, d)), jnp.float32),
            "final_norm": jnp.asarray(1.0 + 0.1 * rng.normal(size=d),
                                      jnp.float32)}
    h = jnp.asarray(rng.normal(size=(dense_gqa.PAD, d)), jnp.float32)
    nxt = jnp.asarray(rng.integers(0, vocab, dense_gqa.PAD), jnp.int32)
    x = np.asarray(h, np.float64)
    x = x / np.sqrt((x * x).mean(-1, keepdims=True) + EPS)
    logits = (x * np.asarray(glob["final_norm"])) @ np.asarray(
        glob["embed"], np.float64).T
    return glob, h, nxt, logits


def test_gap_and_mass_above_by_hand():
    glob, h, nxt, logits = _head_case()
    t = 0.7
    r = dense_gqa.head_readings(EPS, None, False, glob, h, h, nxt,
                                jnp.float32(t), jax.random.key(0))
    own = logits[np.arange(len(nxt)), np.asarray(nxt)]
    p = np.exp((logits - logits.max(-1, keepdims=True)) / t)
    p /= p.sum(-1, keepdims=True)
    mass = (p * (logits > own[:, None])).sum(-1)
    np.testing.assert_allclose(r["gap"], logits.max(-1) - own, atol=1e-4)
    np.testing.assert_allclose(r["mass_above"], mass, atol=1e-5)


def test_the_top_token_has_no_mass_above():
    glob, h, _, logits = _head_case(1)
    top = jnp.asarray(logits.argmax(-1), jnp.int32)
    r = dense_gqa.head_readings(EPS, None, True, glob, h, h, top,
                                jnp.float32(0.7), jax.random.key(1))
    assert float(jnp.max(r["gap"])) == 0.0
    assert float(jnp.max(r["mass_above"])) == 0.0
    # the draw from the whole distribution often is not the top token
    assert 0.0 < float(jnp.mean(r["drawn_mass_above"] > 0)) < 1.0


def test_nucleus_excess_is_the_largest_mass_above_past_top_p():
    limits = {"compared_tokens": ">= 1", "logit_gap": "<= 0.1",
              "sampled_tokens": ">= 1", "nucleus_excess": "<= 0.01"}
    r = {"greedy": np.array([True, False, False]),
         "gap": np.array([0.05, 9.0, 9.0]),
         "mass_above": np.array([0.0, 0.3, 0.95]),
         "top_p": np.array([1.0, 0.9, 0.9])}
    nums = check.numbers(r, limits)
    assert nums["logit_gap"]["value"] == 0.05  # greedy positions only
    assert abs(nums["nucleus_excess"]["value"] - 0.05) < 1e-12
    assert not check.all_within(nums)
    r["mass_above"][2] = 0.5
    nums = check.numbers(r, limits)
    assert nums["nucleus_excess"]["value"] == 0.0
    assert check.all_within(nums)


def test_sample_keeps_the_longest_and_takes_all_on_null():
    items = [(traffic.Arrival(0.0, [1] * 4, n, 0.0, 1.0, i), [2] * n)
             for i, n in enumerate([30, 200, 50, 60, 70, 80, 90])]
    some = check.sample(items, 7, 100, 11)
    assert len(some[0][1]) == 200 and len(some) == check.MIN_REQUESTS
    assert check.sample(items, 7, 100, 11) == some  # drawn from the seed
    assert len(check.sample(items, 7, None, 11)) == len(items)
