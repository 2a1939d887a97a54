"""Roofline machinery: HLO collective parsing, extrapolation, terms."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.roofline import hw
from repro.roofline.analysis import (
    CellCost, collective_bytes, extrapolate, _shape_bytes,
)


def test_shape_bytes_parsing():
    assert _shape_bytes("bf16[4,128]") == 4 * 128 * 2
    assert _shape_bytes("f32[2,3,4]{2,1,0}") == 24 * 4
    assert _shape_bytes("(f32[8], s32[2,2])") == 32 + 16
    assert _shape_bytes("u8[1024]") == 1024
    assert _shape_bytes("pred[]") == 1  # scalar: empty dims -> 1 element


def test_collective_parse_from_real_compile():
    import subprocess, sys, os  # noqa: E401
    code = """
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.distributed.sharding import make_mesh
from repro.roofline.analysis import collective_bytes
mesh = make_mesh((4,), ("model",))
def f(x, w):
    y = x @ w  # w sharded on contracting dim -> psum
    return jax.lax.with_sharding_constraint(
        y, NamedSharding(mesh, P(None, None)))
xs = jax.ShapeDtypeStruct((8, 64), jnp.float32)
ws = jax.ShapeDtypeStruct((64, 32), jnp.float32)
comp = jax.jit(f, in_shardings=(
    NamedSharding(mesh, P(None, "model")),
    NamedSharding(mesh, P("model", None)))).lower(xs, ws).compile()
cb = collective_bytes(comp.as_text())
assert "all-reduce" in cb, cb
assert cb["all-reduce"] >= 8 * 32 * 4, cb
print("OK")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr


def test_extrapolation_linear():
    costs = {
        2: (10.0, 100.0, {"all-reduce": 6.0}),
        4: (16.0, 140.0, {"all-reduce": 10.0, "all-gather": 2.0}),
    }
    cell = extrapolate(costs, 10.0)
    assert cell.flops == 10.0 + (3.0 * 10.0) - 6.0 + 0  # base 4 + 3/unit
    np.testing.assert_allclose(cell.flops, 4.0 + 3.0 * 10.0)
    np.testing.assert_allclose(cell.bytes_hbm, 60.0 + 20.0 * 10.0)
    np.testing.assert_allclose(cell.coll_breakdown["all-reduce"],
                               2.0 + 2.0 * 10.0)
    # all-gather only at depth 4: slope 1, base -2 -> clamped at >= 0
    np.testing.assert_allclose(cell.coll_breakdown["all-gather"], 8.0)


def test_terms_and_dominant():
    cell = CellCost(flops=hw.PEAK_FLOPS_BF16, bytes_hbm=hw.HBM_BW * 2,
                    coll_bytes=hw.ICI_BW * 0.5, coll_breakdown={})
    t = cell.terms()
    assert t["compute_s"] == 1.0
    assert t["memory_s"] == 2.0
    assert t["collective_s"] == 0.5
    assert cell.dominant() == "memory_s"
