"""Jitted wrapper for the Mamba2 SSD Pallas kernel.

Forward uses the kernel; backward falls back to jax.vjp through the
`ssd_chunked` jnp implementation (recompute), matching the train loop's
remat discipline.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import kernels
from repro.kernels.mamba2 import kernel as K
from repro.kernels.mamba2.ref import ssd_chunked


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mamba2_ssd(
    x: jax.Array,  # [B, L, H, P]
    dt: jax.Array,  # [B, L, H] (softplus'd)
    a: jax.Array,  # [H] (negative)
    b: jax.Array,  # [B, L, G, N]
    c: jax.Array,  # [B, L, G, N]
    d: jax.Array,  # [H]
    *,
    chunk: int = 128,
    initial_state: jax.Array | None = None,
    interpret: bool | None = None,
):
    """Pallas-forward chunked SSD. Returns (y [B,L,H,P], final_state)."""
    interpret = kernels.interpret_mode(interpret)
    bsz, l, h, p = x.shape
    n = b.shape[-1]
    g = b.shape[2]
    assert l % chunk == 0
    nc = l // chunk

    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    bm = jnp.repeat(b, h // g, axis=2).astype(jnp.float32)
    cm = jnp.repeat(c, h // g, axis=2).astype(jnp.float32)

    la = dtf * a[None, None, :]
    a_cum = jnp.cumsum(
        la.reshape(bsz, nc, chunk, h), axis=2
    )  # [B,nc,Q,H]

    # to kernel layout [B, H, nc, Q, ·]
    xdt = (xf * dtf[..., None]).reshape(bsz, nc, chunk, h, p)
    xdt = xdt.transpose(0, 3, 1, 2, 4)
    bk = bm.reshape(bsz, nc, chunk, h, n).transpose(0, 3, 1, 2, 4)
    ck = cm.reshape(bsz, nc, chunk, h, n).transpose(0, 3, 1, 2, 4)
    ak = a_cum.transpose(0, 3, 1, 2)  # [B,H,nc,Q]
    s0 = (
        jnp.zeros((bsz, h, n, p), jnp.float32)
        if initial_state is None
        else initial_state.astype(jnp.float32)
    )

    y, s_fin = K.ssd_chunked_fwd(xdt, bk, ck, ak, s0, interpret=interpret)
    y = y.transpose(0, 2, 3, 1, 4).reshape(bsz, l, h, p)
    y = y + xf * d[None, None, :, None]
    return y.astype(x.dtype), s_fin


def mamba2_ssd_trainable(x, dt, a, b, c, d, *, chunk=128, initial_state=None):
    """Differentiable path (jnp chunked form) — used inside train_step."""
    return ssd_chunked(x, dt, a, b, c, d, chunk=chunk, initial_state=initial_state)
