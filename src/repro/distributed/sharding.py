"""Logical-axis sharding: model code names axes, the launcher binds rules.

Model/serving/training code calls `constrain(x, 'batch', 'seq', 'embed')`.
When a mesh + rule set is active (set by the launcher or dryrun via
`use_rules`), this becomes jax.lax.with_sharding_constraint with the mapped
PartitionSpec; otherwise it is a no-op, so the same model code runs on a
laptop CPU and on a 512-chip mesh.

Rule sets:
  TP-only        ('tensor')      heads/ff/vocab on 'model'
  FSDP           ('fsdp')        + weights sharded on ('data',) too (ZeRO-3);
                                 GSPMD inserts the per-layer all-gathers that
                                 overlap with compute
  pods           the 'pod' axis composes with 'data' for batch/grad sharding
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


def make_mesh(shape, axes) -> Mesh:
    """`jax.make_mesh` over the first prod(shape) devices with every axis
    `Auto`: jax.make_mesh defaults to `Explicit` axes, which
    `with_sharding_constraint` (`constrain`) and the GSPMD-partitioned
    jits here do not accept."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape))


@dataclasses.dataclass(frozen=True)
class ShardCtx:
    """Mesh position of a manually-sharded (shard_map) model invocation.

    Threaded as static metadata through `apply_unified` -> `attention` so
    per-device code knows which named axis to all-gather over and how many
    ways the head axis was split.  Hashable/frozen: safe to close over in
    the functools.partial bodies jit caches on.
    """

    axis: str = "tp"
    size: int = 1


_state = threading.local()


def _rules() -> dict | None:
    return getattr(_state, "rules", None)


def _mesh() -> Mesh | None:
    return getattr(_state, "mesh", None)


# logical axis -> mesh axes (None = replicated)
def make_rules(*, multi_pod: bool = False, fsdp: bool = False,
               sp: bool = False) -> dict:
    batch = ("pod", "data") if multi_pod else ("data",)
    wdata = batch if fsdp else None  # weight-sharding data axes (ZeRO-3)
    return {
        "batch": batch,
        "seq": None,
        # residual-stream sequence axis (Megatron-style sequence parallelism:
        # shards the remat-saved activations; GSPMD converts the TP
        # all-reduces into all-gather + reduce-scatter pairs around blocks)
        "seq_sp": ("model",) if sp else None,
        "embed": None,
        "heads": ("model",),
        "kv_heads": ("model",),
        "q_lora": None,
        "ff": ("model",),
        "vocab": ("model",),
        "experts": ("model",),
        "expert_cap": None,
        "pages": batch,  # KV pages sharded like the batch that owns them
        "page_slot": None,
        "head_dim": None,
        "state": None,
        # weight-only logical axes
        "w_embed_in": wdata,  # the non-model dim of weight matrices
        "w_stack": None,  # stacked-layer leading dim
    }


@contextlib.contextmanager
def use_rules(mesh: Mesh, rules: dict):
    prev = (_mesh(), _rules())
    _state.mesh, _state.rules = mesh, rules
    try:
        yield
    finally:
        _state.mesh, _state.rules = prev


def spec(*logical_axes: str | None) -> P:
    rules = _rules()
    assert rules is not None, "spec() needs active rules (use_rules)"
    out = []
    for ax in logical_axes:
        out.append(None if ax is None else rules.get(ax))
    return P(*out)


def constrain(x: jax.Array, *logical_axes: str | None) -> jax.Array:
    """Annotate intermediate sharding; no-op without an active rule set.
    Dims not divisible by their mesh-axis product fall back to replicated
    (e.g. 2 KV heads over a 16-way model axis, or batch 1 in long_500k)."""
    rules, mesh = _rules(), _mesh()
    if rules is None or mesh is None:
        return x
    if x.ndim != len(logical_axes):
        raise ValueError(f"rank {x.ndim} vs axes {logical_axes}")
    out = []
    used: set = set()
    for dim, ax in zip(x.shape, logical_axes):
        mesh_axes = None if ax is None else rules.get(ax)
        if mesh_axes is not None:
            size = 1
            for a in mesh_axes:
                size *= mesh.shape[a]
            if dim % size or used & set(mesh_axes):
                mesh_axes = None  # non-divisible or axis already used
        if mesh_axes is not None:
            used |= set(mesh_axes)
        out.append(mesh_axes)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*out))
    )


def named_sharding(mesh: Mesh, *logical_axes: str | None) -> NamedSharding:
    return NamedSharding(mesh, spec(*logical_axes))


def active() -> bool:
    return _rules() is not None and _mesh() is not None
