"""Production mesh construction (v5e pod geometry).

Single pod:  (data=16, model=16)       = 256 chips (one 16x16 v5e pod)
Multi-pod:   (pod=2, data=16, model=16) = 512 chips; the 'pod' axis carries
             only data-parallel traffic (gradient all-reduce in train, batch
             sharding in serve) because inter-pod DCI bandwidth is far below
             ICI — the sharding rules never place model axes on 'pod'.

XLA flags that matter at scale (set by the real launcher, recorded here):
  --xla_tpu_enable_async_collective_permute=true
  --xla_tpu_enable_latency_hiding_scheduler=true   (overlap comm/compute)
  --xla_tpu_spmd_threshold_for_allgather_cse=10000
Straggler/fault notes live in launch/train.py.
"""
from __future__ import annotations

from repro.distributed.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """The first 256 (512) devices, e.g. of 512 forced host devices."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


RECOMMENDED_XLA_FLAGS = [
    "--xla_tpu_enable_async_collective_permute=true",
    "--xla_tpu_enable_async_all_gather=true",
    "--xla_tpu_enable_latency_hiding_scheduler=true",
    "--xla_tpu_megacore_fusion_allow_ags=true",
]
