"""Seeded weights, made on the device.

Every tensor is a pure function of (seed, layer, tensor name).  Its values
are `(u - 1.5) * 2**e (+ offset)` with `u` in [1, 2) built from random
bits, so every float operation before the one rounding to the served dtype
is exact.  The stacked generator that feeds the program and the
layer-by-layer generator that feeds the reference therefore give the same
bits on any backend, without either one reading what the other made.

Which tensors a model has, with their shapes, exponents and offsets, is
its reference module's `layer_layout` / `global_layout`
(`bench/configs/<reference>.py`).
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

from bench.configs import reference

GLOBAL_LAYER = 0x7FFFFFFF  # layer index of the tensors outside the stack


def scale_exponent(fan_in: int) -> int:
    """Power-of-two half-width nearest sqrt(3 / fan_in): unit-variance
    inputs give outputs of about unit variance."""
    return round(math.log2(math.sqrt(3.0 / fan_in)))


def seed_key(seed: int):
    """A PRNG key for any seed below 2**64 (the low 32 bits seed it, the
    high bits are folded in)."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _tensor(base, layer, name, shape, e, offset, dtype):
    key = jax.random.fold_in(jax.random.fold_in(base, layer),
                             zlib.crc32(name.encode()) & 0x7FFFFFFF)
    bits = jax.random.bits(key, shape, jnp.uint32)
    u = jax.lax.bitcast_convert_type((bits >> 9) | jnp.uint32(0x3F800000),
                                     jnp.float32)
    x = (u - 1.5) * (2.0 ** e)
    if offset:
        x = x + offset
    return x.astype(dtype)


def layer_weights(spec: dict, base, layer, dtype=jnp.bfloat16) -> dict:
    """One layer's tensors from the run's key `base` (`seed_key`); under
    jit `base` and `layer` may be traced, so one program serves every
    seed."""
    return {n: _tensor(base, layer, n, s, e, o, dtype)
            for n, (s, e, o) in reference(spec).layer_layout(spec).items()}


def global_weights(spec: dict, base, dtype=jnp.bfloat16) -> dict:
    return {n: _tensor(base, GLOBAL_LAYER, n, s, e, o, dtype)
            for n, (s, e, o) in reference(spec).global_layout(spec).items()}


def stacked_weights(spec: dict, base, dtype=jnp.bfloat16) -> dict:
    """Every layer's tensors stacked on a leading layer axis, plus the
    global ones under "global" (call under jit)."""
    layers = jax.lax.map(lambda i: layer_weights(spec, base, i, dtype),
                         jnp.arange(spec["num_hidden_layers"],
                                    dtype=jnp.int32))
    return {"layers": layers, "global": global_weights(spec, base, dtype)}
