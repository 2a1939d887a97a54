"""Distribution layer tests — run in subprocesses with
XLA_FLAGS=--xla_force_host_platform_device_count so the main test process
keeps a single device (per the dry-run isolation rule)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(code: str, n: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=600,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    return out.stdout


def test_sharding_rules_roundtrip():
    run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.distributed import sharding as sh
from repro.distributed.sharding import make_mesh
mesh = make_mesh((2, 2), ("data", "model"))
rules = sh.make_rules()
with sh.use_rules(mesh, rules):
    assert sh.spec("batch", "seq", "heads", None) == \
        jax.sharding.PartitionSpec(("data",), None, ("model",), None)
    @jax.jit
    def f(x):
        return sh.constrain(x * 2, "batch", "embed")
    x = jnp.ones((4, 8))
    y = f(x)
    np.testing.assert_array_equal(np.asarray(y), 2 * np.ones((4, 8)))
print("OK")
""", n=4)


def test_int8_error_feedback_allreduce():
    run_with_devices("""
import functools
import jax, jax.numpy as jnp, numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P
from repro.distributed.collectives import int8_ef_allgather, bf16_psum
from repro.distributed.sharding import make_mesh

mesh = make_mesh((4,), ("data",))
x = jnp.arange(32, dtype=jnp.float32).reshape(4, 8) / 7.3
ef0 = jnp.zeros((8,), jnp.float32)

@jax.jit
def summed(x, ef):
    def body(xl, efl):
        tree, new_ef = int8_ef_allgather(xl[0], "data", efl[0])
        return tree[None], new_ef[None]
    return shard_map(body, mesh=mesh, in_specs=(P("data"), P("data")),
                     out_specs=(P("data"), P("data")))(x, ef)

exact = np.asarray(x).sum(0)
s, ef = summed(x, jnp.tile(ef0[None], (4, 1)))
s = np.asarray(s)[0]
rel = np.abs(s - exact).max() / np.abs(exact).max()
assert rel < 0.02, rel  # int8 quantization error, one step
# error feedback accumulates the residual -> running average is unbiased
acc = np.zeros_like(exact); efc = jnp.tile(ef0[None], (4, 1))
for i in range(50):
    s, efc = summed(x, efc)
    acc += np.asarray(s)[0]
rel50 = np.abs(acc / 50 - exact).max() / np.abs(exact).max()
assert rel50 < 0.002, rel50  # EF drives the time-averaged error down

@jax.jit
def bsum(x):
    def body(xl):
        return bf16_psum(xl[0], "data")[None]
    return shard_map(body, mesh=mesh, in_specs=(P("data"),),
                     out_specs=P("data"))(x)
sb = np.asarray(bsum(x))[0]
assert np.abs(sb - exact).max() / np.abs(exact).max() < 0.01
print("OK")
""")


def test_ep_moe_matches_dropless():
    """Fully-manual shard_map EP MoE == single-host dropless MoE
    (the §Perf B2 optimization is numerics-free)."""
    run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import ARCHS, reduced
from repro.distributed import sharding as SH
from repro.distributed.sharding import make_mesh
from repro.models.moe import init_moe, moe_ffn_dropless, moe_ffn_dropless_ep

mesh = make_mesh((2, 4), ("data", "model"))
cfg = reduced(ARCHS["deepseek-v2-236b"]).replace(dtype="float32")
p = init_moe(cfg, jax.random.key(0))
x = jax.random.normal(jax.random.key(1), (2, 16, cfg.d_model))
ref, _ = moe_ffn_dropless(cfg, p, x)
with SH.use_rules(mesh, SH.make_rules()):
    got, _ = jax.jit(lambda p, x: moe_ffn_dropless_ep(cfg, p, x))(p, x)
np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                           atol=2e-5, rtol=2e-5)
print("OK")
""")


def test_pipeline_matches_sequential():
    run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.pipeline import pipeline_apply, stage_split
from repro.distributed.sharding import make_mesh

mesh = make_mesh((4,), ("pipe",))
L, M, mb, d = 8, 6, 2, 16
key = jax.random.key(0)
w = jax.random.normal(key, (L, d, d)) * 0.3

def layer(wl, x):
    return jnp.tanh(x @ wl)

def stage_fn(params, x):  # params [L/S, d, d]
    def body(x, wl):
        return layer(wl, x), None
    x, _ = jax.lax.scan(body, x, params)
    return x

x = jax.random.normal(jax.random.key(1), (M, mb, d))
# sequential reference
ref = x
for i in range(L):
    ref = layer(w[i], ref)
got = pipeline_apply(stage_fn, stage_split({"w": w}, 4)["w"], x, mesh=mesh)
np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)
print("OK")
""", n=4)
