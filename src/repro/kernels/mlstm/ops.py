"""Jitted wrapper for the chunkwise mLSTM Pallas kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import kernels
from repro.kernels.mlstm import kernel as K
from repro.kernels.mlstm.ref import mlstm_chunked


def _logsigmoid(x):
    return -jax.nn.softplus(-x)


@functools.partial(jax.jit, static_argnames=("chunk", "scale", "interpret"))
def mlstm(
    q: jax.Array,  # [B, L, H, P]
    k: jax.Array,
    v: jax.Array,
    igate: jax.Array,  # [B, L, H] preactivations
    fgate: jax.Array,
    *,
    chunk: int = 64,
    scale: float | None = None,
    interpret: bool | None = None,
):
    """Pallas chunkwise mLSTM. Returns (h [B,L,H,P], (C, n, m) final)."""
    interpret = kernels.interpret_mode(interpret)
    bsz, l, h, p = q.shape
    if scale is None:
        scale = p**-0.5
    assert l % chunk == 0
    nc = l // chunk

    def to_k(x):  # [B,L,H,...] -> [B,H,nc,Q,...]
        x = x.reshape((bsz, nc, chunk) + x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    qf = to_k(q.astype(jnp.float32))
    kf = to_k(k.astype(jnp.float32) * scale)
    vf = to_k(v.astype(jnp.float32))
    ig = to_k(igate.astype(jnp.float32))
    lf = to_k(_logsigmoid(fgate.astype(jnp.float32)))
    bcum = jnp.cumsum(lf, axis=3)
    g = jax.lax.cummax(ig - bcum, axis=3)

    hs, c, n, m = K.mlstm_chunked_fwd(qf, kf, vf, ig, bcum, g,
                                      interpret=interpret)
    hs = jnp.moveaxis(hs, 1, 3).reshape(bsz, l, h, p).astype(q.dtype)
    return hs, (c, n[:, :, 0, :], m[:, :, 0])


def mlstm_trainable(q, k, v, igate, fgate, *, chunk=64, initial_state=None):
    """Differentiable path (jnp chunkwise form) — used inside train_step."""
    return mlstm_chunked(q, k, v, igate, fgate, chunk=chunk,
                         initial_state=initial_state)
