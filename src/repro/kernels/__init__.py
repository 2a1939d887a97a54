"""Pallas TPU kernels, each as <name>/{kernel.py, ops.py, ref.py}.

Kernels target TPU (pl.pallas_call + BlockSpec VMEM tiling + scalar-prefetch
block-table indirection) and are validated on CPU in interpret mode against
the pure-jnp oracles in ref.py.

  paged_attention/  the paper's contribution: C1 baseline, C2 GQA Q-Block,
                    C3 parallel tiled softmax (+ reduction), C4 adjustable
                    tiles, C5 static launch grid.
  flash_attention/  training-side causal flash attention (GQA), fwd kernel +
                    differentiable scan oracle used as the XLA backend.
  mamba2/           chunked SSD scan for hybrid archs (zamba2).
  mlstm/            xLSTM matrix-memory chunkwise kernel.
"""
from __future__ import annotations

import jax


def interpret_mode(interpret: bool | None = None) -> bool:
    """Whether a kernel call runs in Pallas interpret mode: an explicit
    `interpret` wins; otherwise kernels compile natively on a TPU backend
    and are interpreted everywhere else (the CPU test suite).  The ops
    modules call this through the package attribute, so a test that
    compiles for a described TPU while the backend is still the CPU can
    patch it."""
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"
