"""The program and the reference get the same weights without either
reading what the other made."""
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

from bench import system, weights
from bench.configs import reference

CELLS = pathlib.Path(__file__).resolve().parent / "data" / "cells"
SPEC = json.loads((CELLS / "configs" / "tiny.json").read_text())
SEED = 2_147_483_659


def test_stacked_equals_layer_by_layer():
    base = weights.seed_key(SEED)
    stacked = jax.jit(lambda b: weights.stacked_weights(SPEC, b))(base)
    for layer in range(SPEC["num_hidden_layers"]):
        one = jax.jit(lambda b, i: weights.layer_weights(SPEC, b, i))(
            base, jnp.int32(layer))
        for name, w in one.items():
            np.testing.assert_array_equal(
                np.asarray(stacked["layers"][name][layer], np.float32),
                np.asarray(w, np.float32), err_msg=name)
    glob = weights.global_weights(SPEC, base)
    np.testing.assert_array_equal(np.asarray(stacked["global"]["embed"]),
                                  np.asarray(glob["embed"]))


def test_seeds_differ_and_repeat():
    a = weights.global_weights(SPEC, weights.seed_key(SEED))["embed"]
    b = weights.global_weights(SPEC, weights.seed_key(SEED))["embed"]
    c = weights.global_weights(SPEC, weights.seed_key(SEED + 2**32))["embed"]
    assert np.array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))


def test_program_tree_matches_the_program():
    cfg = system.model_config(SPEC)
    params = jax.jit(lambda b: reference(SPEC).program_params(
        cfg, weights.stacked_weights(SPEC, b)))(weights.seed_key(1))
    system.check_params(cfg, params)


def test_a_departure_from_the_file_is_refused():
    bad = dict(SPEC, rope_theta=500000.0)
    try:
        system.model_config(bad)
    except SystemExit as e:
        assert "rope_theta" in str(e)
    else:
        raise AssertionError("a changed rope_theta was accepted")
