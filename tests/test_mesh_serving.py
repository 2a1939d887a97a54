"""Mesh-aware packed serving: differential equivalence + sharding contract.

The mesh executor's whole promise is observational invisibility: tensor
parallelism changes WHERE head blocks are computed, never WHAT tokens come
out.  The core test here drives the mixed chunked+cached+preempt harness
trace through tp={1,2,4} on a forced-host-device mesh and asserts
token-for-token identity plus the one-dispatch-per-step invariant; the
rest pins the KV head-split shard specs, the per-device/aggregate pool
stats, and the structured ShardingError paths.

Multi-device cases run in subprocesses (`XLA_FLAGS=--xla_force_host_
platform_device_count=N` must be set before the backend initializes);
the in-process tests are device-count agnostic.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import serving_harness as H
from repro.core.attention import heuristics
from repro.core.paged import kv_cache as KV
from repro.core.paged.allocator import RefCountedPageAllocator
from repro.serving import executor as X

_TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_TESTS_DIR)


def run_with_devices(code: str, n: int = 4) -> str:
    """Run `code` in a fresh python with n forced host devices; the main
    pytest process keeps its own (usually single-device) backend."""
    env = dict(os.environ)
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "host_platform_device_count" not in f]
    flags.append(f"--xla_force_host_platform_device_count={n}")
    env["XLA_FLAGS"] = " ".join(flags)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(_ROOT, "src"), _TESTS_DIR]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, (
        f"child failed (rc={r.returncode})\n--- stdout ---\n{r.stdout}"
        f"\n--- stderr ---\n{r.stderr}")
    return r.stdout


# ---------------------------------------------------------------------------
# the acceptance differential: tp={1,2,4} token-for-token on the mixed
# chunked + prefix-cached + preempting trace (one engine family per child)
# ---------------------------------------------------------------------------


def test_tp_differential_mixed_chunked_cached_preempt():
    run_with_devices("""
import numpy as np
import serving_harness as H

# reduced smollm has 2 q / 1 kv head — not tp-divisible; widen the head
# axis (same d_model) so tp=4 still holds whole GQA groups per device
cfg, params = H.build_cfg_params(num_q_heads=8, num_kv_heads=4)
rng = np.random.default_rng(3)
prompts = H.make_prompts(cfg, rng, (3 * 16 + 10, 3 * 16 + 2))
runs = {}
for tp in (1, 2, 4):
    eng = H.build_engine(cfg, params, tp=tp, max_seqs=2, num_pages=8,
                         max_model_len=128,
                         enable_chunked_prefill=True,
                         enable_prefix_caching=True,
                         max_prefill_tokens=16)
    runs[tp] = H.run_requests(eng, prompts, max_new_tokens=8)
    # ONE device dispatch per steady step, at every tp (a shard_map-
    # wrapped jit is still a single launch)
    assert eng.device_calls == {"unified": runs[tp].num_steps}, \\
        (tp, dict(eng.device_calls))
assert runs[1].total("preempted") > 0, "trace must exercise preemption"
assert runs[1].total("partial_prefills") > 0, \\
    "trace must exercise chunked (resumed-prefill) steps"
for tp in (2, 4):
    H.assert_same_outputs(runs[1], runs[tp], label_a="tp1",
                          label_b=f"tp{tp}")
print("OK")
""", n=4)


def test_tp1_executor_is_single_device_and_matches_reference():
    """tp=1 must degenerate to the pre-executor path: the same jit-of-
    apply_unified partial (SingleDeviceExecutor), producing the dense
    greedy reference bit-for-bit."""
    cfg, params = H.build_cfg_params()
    eng = H.build_engine(cfg, params, tp=1)
    assert type(eng.executor) is X.SingleDeviceExecutor
    rng = np.random.default_rng(7)
    prompts = H.make_prompts(cfg, rng, (13, 5))
    res = H.run_requests(eng, prompts, max_new_tokens=6)
    for p, out in zip(prompts, res.outputs):
        assert out == H.greedy_reference(cfg, params, p, 6)


# ---------------------------------------------------------------------------
# shard-spec round-trip for the KV head split
# ---------------------------------------------------------------------------


def test_kv_head_shard_spec_round_trip():
    run_with_devices("""
import jax
import jax.numpy as jnp
import numpy as np
from repro.core.paged import kv_cache as KV
from repro.distributed import param_sharding as PS
from repro.distributed.sharding import make_mesh

mesh = make_mesh((4,), ("tp",))
specs = KV.make_kv_cache_specs(2, 8, 1, 6, 4, 16, 16, "float32")
assert list(specs) == ["kv_pages"], list(specs)  # one pool, K|V rows
local = KV.shard_cache_specs(specs, 4)
assert local["kv_pages"].shape == (2, 2, 1, 6, 4, 32), local["kv_pages"].shape

sh = PS.assign_cache_shardings({"attn": specs}, mesh=mesh, batch_axes=(),
                               model_axis="tp")["attn"]
# head axis (dim 1) on "tp", everything else replicated
spec = sh["kv_pages"].spec
assert spec[1] == "tp", spec
assert all(s is None for i, s in enumerate(spec) if i != 1), spec

# round-trip: place a counting array, check each device holds its
# CONTIGUOUS head block in mesh order, and reassembly is exact
shape = specs["kv_pages"].shape
arr = jnp.arange(np.prod(shape), dtype=jnp.float32).reshape(shape)
placed = jax.device_put(arr, sh["kv_pages"])
starts = {}
for s in placed.addressable_shards:
    sl = s.index[1]
    starts[s.device.id] = sl.start
    np.testing.assert_array_equal(np.asarray(s.data), np.asarray(arr[s.index]))
order = [d.id for d in mesh.devices.flat]
assert [starts[i] for i in order] == [0, 2, 4, 6], starts
np.testing.assert_array_equal(np.asarray(placed), np.asarray(arr))
print("OK")
""", n=4)


def test_serve_param_specs_shard_only_qkv_heads():
    """Under tp no param is sharded, q/k/v projections included: every
    device projects all heads with the tp=1 matmul shapes (a head-sliced
    projection compiles to a different program on a TPU and breaks
    token identity with tp=1) and keeps its own head block."""
    run_with_devices("""
import jax
from jax.sharding import PartitionSpec as P
import serving_harness as H
from repro.serving import executor as X

cfg, params = H.build_cfg_params(num_q_heads=8, num_kv_heads=4)
ex = X.TensorParallelExecutor(cfg, tp=4, backend="xla", max_seqs=2,
                              fused=True, seed=0, debug_logits=False)
placed = ex.place_params(params)
for path, leaf in jax.tree_util.tree_flatten_with_path(placed)[0]:
    assert leaf.sharding.spec == P(), (jax.tree_util.keystr(path),
                                       leaf.sharding.spec)
    assert len(leaf.sharding.device_set) == 4, jax.tree_util.keystr(path)
print("OK")
""", n=4)


# ---------------------------------------------------------------------------
# structured ShardingError paths
# ---------------------------------------------------------------------------


def test_sharding_errors_in_process():
    cfg, params = H.build_cfg_params()  # 2 q / 1 kv head

    # head counts not divisible by tp (checked before device count, so
    # this works on a single-device pytest process)
    with pytest.raises(KV.ShardingError, match="num_kv_heads=1"):
        H.build_engine(cfg, params, tp=2)

    # the padded per-kind path never runs under a mesh
    with pytest.raises(KV.ShardingError, match="packed"):
        H.build_engine(cfg, params, tp=2, packed_attention=False)

    # pipeline parallelism is an interface stub
    with pytest.raises(NotImplementedError, match="pp=2"):
        X.make_executor(cfg, backend="xla", tp=1, pp=2, max_seqs=2,
                        fused=True, seed=0, debug_logits=False)

    # helper-level divisibility validation
    with pytest.raises(KV.ShardingError, match="num_kv_heads=3"):
        KV.local_kv_heads(3, 2)
    with pytest.raises(KV.ShardingError, match="num_q_heads=6"):
        KV.local_kv_heads(4, 4, num_q_heads=6)
    assert KV.local_kv_heads(8, 4, num_q_heads=16) == 2


def test_insufficient_devices_error_names_the_flag():
    run_with_devices("""
import serving_harness as H
from repro.core.paged.kv_cache import ShardingError

cfg, params = H.build_cfg_params(num_q_heads=8, num_kv_heads=4)
try:
    H.build_engine(cfg, params, tp=4)
except ShardingError as e:
    assert "xla_force_host_platform_device_count" in str(e), e
    print("OK")
else:
    raise AssertionError("tp=4 on 1 device must raise ShardingError")
""", n=1)


# ---------------------------------------------------------------------------
# per-device pool views + mesh fingerprints
# ---------------------------------------------------------------------------


def test_mesh_stats_aggregate_and_per_device():
    alloc = RefCountedPageAllocator(16, 4)
    pages = alloc.allocate(3)
    base = alloc.stats()
    agg = alloc.mesh_stats(4)
    assert agg["num_devices"] == 4 and len(agg["per_device"]) == 4
    for k, v in base.items():
        assert agg[k] == 4 * v, (k, agg[k], v)
    for d, dev in enumerate(agg["per_device"]):
        assert dev["device"] == d
        assert {k: dev[k] for k in base} == base
    # num_devices=1 is exactly stats() (existing consumers unaffected)
    one = alloc.mesh_stats(1)
    assert {k: one[k] for k in base} == base
    alloc.free(pages)


def test_batch_profile_mesh_fingerprint():
    p = heuristics.BatchProfile(num_seqs=2, max_context=64, group=2,
                                page_size=16, tp=4)
    assert p.tp == 4
    assert heuristics.BatchProfile(
        num_seqs=2, max_context=64, group=2, page_size=16).tp == 1
    # the telemetry latency grid serializes the profile positionally;
    # tp must survive the astuple -> named-dict round trip
    from repro.obs import Telemetry
    from repro.obs.clock import FakeClock
    tel = Telemetry(clock=FakeClock())
    tel.set_arch(tp=4)
    tel.record_launch("unified", p, heuristics.KernelConfig("gqa"),
                      0.0, 1.0, compiled=False, tokens=32,
                      grid_phase="unified")
    grid = tel.latency_grid()
    assert grid["arch"]["tp"] == 4
    assert grid["entries"][0]["profile"]["tp"] == 4
