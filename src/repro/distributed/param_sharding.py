"""Parameter / optimizer-state / cache sharding-spec assignment.

Path+shape-based rules with divisibility guards, so the same assigner covers
every assigned architecture:

  * expert weights (w_gate/w_up/w_down, [.., E, d, ff]): E on 'model' (EP),
    d on the FSDP data axes when enabled;
  * embedding tables ([V, d]): V on 'model' (vocab-parallel logits), d on
    FSDP axes;
  * generic >=2-D weights: of the LAST TWO dims, the larger divisible dim
    goes on 'model' (TP), the other on the FSDP axes when divisible
    (ZeRO-3); leading stacked-layer dims stay unsharded;
  * 1-D leaves (norm scales, biases, gate vectors): replicated.

Optimizer state (mu/nu) inherits the spec of its parameter (same trailing
path). KV caches / SSM state caches get their own assigner below.
"""
from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

EXPERT_NAMES = ("w_gate", "w_up", "w_down")

# §Perf knob: shard KV-page head_dim over 'model' when KV heads don't
# divide it (True), vs replicate KV within each pool (False).
KV_HEADDIM_SHARD = True

# Megatron pairing: column-parallel ops shard the OUTPUT dim (no comm),
# row-parallel ops shard the INPUT dim (their input arrives already sharded
# from the preceding column-parallel op; one psum — or, with sequence
# parallelism, a reduce-scatter — closes the block).
ROW_PARALLEL = ("wo", "down", "out_proj")


def _axes_size(mesh: Mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _is_row_parallel(path: str) -> bool:
    return any(f"'{n}'" in path for n in ROW_PARALLEL)


def param_spec(path: str, leaf, *, mesh: Mesh, fsdp: bool,
               batch_axes: tuple[str, ...]) -> P:
    shape = leaf.shape
    model_n = mesh.shape["model"]
    data_n = _axes_size(mesh, batch_axes)
    wdata = tuple(batch_axes) if fsdp else None

    if leaf.ndim == 0:
        return P()
    # expert weights: [(L,) E, d, ff]
    if any(f"'{n}'" in path for n in EXPERT_NAMES) and leaf.ndim >= 3:
        spec = [None] * leaf.ndim
        e_dim = leaf.ndim - 3
        if shape[e_dim] % model_n == 0:
            spec[e_dim] = "model"
        if wdata and shape[e_dim + 1] % data_n == 0:
            spec[e_dim + 1] = wdata
        return P(*spec)
    if leaf.ndim == 1:
        return P(None)
    spec = [None] * leaf.ndim
    d0, d1 = leaf.ndim - 2, leaf.ndim - 1
    # embedding table [V, d]: vocab-parallel (logits come out vocab-sharded)
    if "'table'" in path:
        cand = [d0, d1]
    elif _is_row_parallel(path):
        cand = [d0, d1]  # input dim first (row-parallel)
    else:
        cand = [d1, d0]  # output dim first (column-parallel)
    model_dim = next((i for i in cand if shape[i] % model_n == 0), None)
    if model_dim is not None:
        spec[model_dim] = "model"
    if wdata:
        other = d0 if model_dim == d1 else d1
        if other != model_dim and shape[other] % data_n == 0:
            spec[other] = wdata
    return P(*spec)


def assign_param_shardings(abstract_params, *, mesh: Mesh, fsdp: bool,
                           batch_axes: tuple[str, ...]):
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract_params)
    out = []
    for path, leaf in flat:
        spec = param_spec(jax.tree_util.keystr(path), leaf, mesh=mesh,
                          fsdp=fsdp, batch_axes=batch_axes)
        out.append(NamedSharding(mesh, spec))
    return jax.tree_util.tree_unflatten(treedef, out)


def cache_spec(path: str, leaf, *, mesh: Mesh,
               batch_axes: tuple[str, ...],
               model_axis: str = "model") -> P:
    """Serving-cache sharding.

    KV pool   [L, Hkv, pools, P, ps, Dk+Dv]: pools on the batch axes (each DP
        shard owns one pool), Hkv on the model axis when divisible;
    SSM state [L, B, ...]: B on the batch axes, the head dim on the model
        axis when divisible.

    `model_axis` defaults to the training/dryrun mesh name; the serving
    mesh executor passes its own axis ("tp").
    """
    shape = leaf.shape
    model_n = mesh.shape[model_axis]
    data_n = _axes_size(mesh, batch_axes)
    if "kv_pages" in path:
        spec = [None] * leaf.ndim
        if batch_axes and shape[2] % data_n == 0:
            spec[2] = tuple(batch_axes)
        if shape[1] % model_n == 0:
            spec[1] = model_axis  # prefer KV-head sharding (no score psum)
        elif KV_HEADDIM_SHARD and shape[-1] % model_n == 0:
            # few KV heads (GQA/MLA): shard the K|V row over 'model' — the
            # score contraction then carries a per-tile psum, but the cache
            # fits (llama3-405b decode_32k: 2.1 TB of KV). §Perf also
            # evaluates the replicated-within-pool alternative
            # (KV_HEADDIM_SHARD=False): more HBM, near-zero collectives.
            spec[-1] = model_axis
        return P(*spec)
    # state caches: [L, B, heads?/dim...]
    spec = [None] * leaf.ndim
    if leaf.ndim >= 2 and batch_axes and shape[1] % data_n == 0:
        spec[1] = tuple(batch_axes)
    if leaf.ndim >= 3 and shape[2] % model_n == 0:
        spec[2] = model_axis
    return P(*spec)


def assign_cache_shardings(abstract_cache, *, mesh: Mesh,
                           batch_axes: tuple[str, ...],
                           model_axis: str = "model"):
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract_cache)
    out = []
    for path, leaf in flat:
        spec = cache_spec(jax.tree_util.keystr(path), leaf, mesh=mesh,
                          batch_axes=batch_axes, model_axis=model_axis)
        out.append(NamedSharding(mesh, spec))
    return jax.tree_util.tree_unflatten(treedef, out)


def batch_spec(key: str, leaf, *, mesh: Mesh,
               batch_axes: tuple[str, ...]) -> P:
    data_n = _axes_size(mesh, batch_axes)
    shape = leaf.shape
    if key == "positions" and leaf.ndim == 3:  # mrope [3, B, S]
        bdim = 1
    else:
        bdim = 0
    spec = [None] * leaf.ndim
    if shape[bdim] % data_n == 0:
        spec[bdim] = tuple(batch_axes)
    return P(*spec)


def assign_batch_shardings(batch_specs: dict, *, mesh: Mesh,
                           batch_axes: tuple[str, ...]):
    return {
        k: NamedSharding(mesh, batch_spec(k, v, mesh=mesh,
                                          batch_axes=batch_axes))
        for k, v in batch_specs.items()
    }
