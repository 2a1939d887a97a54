"""Mesh-aware launch layer for the serving engine (docs/serving.md).

The engine schedules and packs on the host exactly as before; WHERE the
packed unified step runs is this module's job.  `make_executor` returns a
`DeviceMeshExecutor` that places params/cache on the mesh once at init and
builds the per-kernel-config unified executables the engine caches:

  SingleDeviceExecutor    tp=1 — literally the pre-refactor jit partial
                          (bit-identical by construction: same callable,
                          same trace)
  TensorParallelExecutor  tp>1 — the step runs under shard_map over a
                          ("tp",) mesh.  ONLY the KV pages are sharded,
                          on the head axis (every device holds
                          num_kv_heads/tp heads of EVERY page, so page
                          tables / slot_mapping / query_start_loc
                          metadata stay replicated and the scheduler is
                          untouched).  Params are replicated: each
                          device projects all q/k/v heads, exactly as
                          tp=1 does, keeps its own head block for
                          attention, and one tiled all-gather of
                          attention outputs precedes the replicated
                          wo/head/sampling epilogue.  Every matmul has
                          its tp=1 shape (on a TPU a head-sliced
                          projection compiles to a different program
                          and rounds differently), so outputs are
                          bit-identical to tp=1, and a shard_map-wrapped
                          jit is still ONE device dispatch per step.
  PipelineParallelExecutor pp>1 — interface stub: micro-batched packed
                          steps slot in behind the same three methods
                          (place_params / place_cache / build_unified)
                          without the engine changing.

Everything is CPU-testable via
`XLA_FLAGS=--xla_force_host_platform_device_count=4`.
"""
from __future__ import annotations

import functools

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.paged.kv_cache import ShardingError, local_kv_heads
from repro.distributed import param_sharding as PS
from repro.distributed import sharding as dsh
from repro.models import model as M


class DeviceMeshExecutor:
    """Contract between the engine and the device mesh.

    * `place_params` / `place_cache` run once at engine init and pin the
      pytrees to their mesh placement (identity on one device).
    * `build_unified(kernel_cfg)` returns the jitted step callable
      `(params, cache, batch) -> apply_unified outputs`; the engine
      caches one per (token-bucket, kernel-config) key and a steady step
      calls it exactly once — the one-dispatch invariant holds for every
      executor.  The callable DONATES its cache: a launch consumes the
      pool it is given and returns the live one, updated in place.
    * Replicated vs sharded is an executor-internal decision; the engine
      never sees specs, only placed pytrees and callables.
    """

    tp: int = 1
    pp: int = 1

    def __init__(self, cfg, *, backend, max_seqs, fused, seed, debug_logits,
                 max_draft=0):
        self.cfg = cfg
        self.backend = backend
        self.max_seqs = max_seqs
        self.fused = fused
        self.seed = seed
        self.debug_logits = debug_logits
        # speculative decoding: K > 0 switches the fused epilogue to the
        # [S, K+1] verify contract (tokens + num_emitted outputs)
        self.max_draft = max_draft

    def place_params(self, params):
        return params

    def place_cache(self, cache):
        return cache

    def build_unified(self, kernel_cfg):
        raise NotImplementedError

    def describe(self) -> dict:
        return {"tp": self.tp, "pp": self.pp}


class SingleDeviceExecutor(DeviceMeshExecutor):
    """Mesh size 1: exactly the pre-executor launch path."""

    def build_unified(self, kernel_cfg):
        return jax.jit(functools.partial(
            M.apply_unified, self.cfg, backend=self.backend,
            kernel_cfg=kernel_cfg, num_decode_seqs=self.max_seqs,
            sample=self.fused, seed=self.seed,
            return_logits=self.debug_logits, max_draft=self.max_draft,
        ), donate_argnums=(1,))


class TensorParallelExecutor(DeviceMeshExecutor):
    """Head-axis tensor parallelism over a ("tp",) mesh."""

    AXIS = "tp"

    def __init__(self, cfg, *, tp, **kw):
        super().__init__(cfg, **kw)
        self.tp = tp
        # whole heads per device (also validates divisibility)
        local_kv_heads(cfg.num_kv_heads, tp, num_q_heads=cfg.num_q_heads)
        if jax.device_count() < tp:
            raise ShardingError(
                f"tp={tp} needs {tp} devices but only "
                f"{jax.device_count()} are visible; on CPU set "
                f"XLA_FLAGS=--xla_force_host_platform_device_count={tp}")
        self.mesh = dsh.make_mesh((tp,), (self.AXIS,))
        self.shard = dsh.ShardCtx(axis=self.AXIS, size=tp)

    def place_params(self, params):
        return jax.device_put(params, NamedSharding(self.mesh, P()))

    def place_cache(self, cache):
        return jax.device_put(cache, PS.assign_cache_shardings(
            cache, mesh=self.mesh, batch_axes=(), model_axis=self.AXIS))

    def _cache_specs(self, cache):
        flat, treedef = jax.tree_util.tree_flatten_with_path(cache)
        out = [PS.cache_spec(jax.tree_util.keystr(p), leaf, mesh=self.mesh,
                             batch_axes=(), model_axis=self.AXIS)
               for p, leaf in flat]
        return jax.tree_util.tree_unflatten(treedef, out)

    def build_unified(self, kernel_cfg):
        body = functools.partial(
            M.apply_unified, self.cfg, backend=self.backend,
            kernel_cfg=kernel_cfg, num_decode_seqs=self.max_seqs,
            sample=self.fused, seed=self.seed,
            return_logits=self.debug_logits, shard=self.shard,
            max_draft=self.max_draft,
        )
        # replicated outputs before the cache: logits OR fused tokens,
        # plus num_emitted under speculation, plus debug logits
        n_out = 1
        if self.fused and self.max_draft:
            n_out += 1
        if self.fused and self.debug_logits:
            n_out += 1

        def run(params, cache, batch):
            # spec trees come from the actual pytrees at trace time, so
            # one wrapper serves every param/cache layout
            pspecs = jax.tree.map(lambda _: P(), params)
            cspecs = self._cache_specs(cache)
            bspecs = jax.tree.map(lambda _: P(), batch)
            # tokens/logits are replicated outputs; the cache comes back
            # sharded exactly as it went in
            out_specs = (P(),) * n_out + (cspecs,)
            return jax.shard_map(
                body, mesh=self.mesh, in_specs=(pspecs, cspecs, bspecs),
                out_specs=out_specs, check_vma=False,
            )(params, cache, batch)

        return jax.jit(run, donate_argnums=(1,))


class PipelineParallelExecutor(DeviceMeshExecutor):
    """Interface stub: micro-batched packed steps over a ("pp",) mesh.

    The executor contract (place once, build per-config callables, one
    logical dispatch per step) is already shaped for it — a micro-batched
    `build_unified` would split the packed token stream into in-flight
    micro-steps device-side, which needs no engine/scheduler change.
    """

    def __init__(self, cfg, *, pp, **kw):
        raise NotImplementedError(
            f"pipeline-parallel packed serving (pp={pp}) is an interface "
            f"stub; only tp meshes execute today")


def make_executor(cfg, *, backend, tp=1, pp=1, max_seqs, fused, seed,
                  debug_logits, packed=True, max_draft=0):
    kw = dict(backend=backend, max_seqs=max_seqs, fused=fused, seed=seed,
              debug_logits=debug_logits, max_draft=max_draft)
    if pp > 1:
        return PipelineParallelExecutor(cfg, pp=pp, **kw)
    if tp > 1:
        if not packed:
            raise ShardingError(
                "the mesh executor only runs the packed unified step; "
                f"tp={tp} with packed_attention=False (padded per-kind "
                f"launches) is not supported")
        return TensorParallelExecutor(cfg, tp=tp, **kw)
    return SingleDeviceExecutor(cfg, **kw)
