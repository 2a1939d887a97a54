"""Dry-run pipeline test: the same lower->compile->roofline machinery as
launch/dryrun.py, exercised on an 8-host-device mesh with reduced configs
(subprocess, so the main test process keeps one device)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CODE = """
import os
import jax
import jax.numpy as jnp
from repro.configs import ARCHS, reduced
from repro.configs.base import InputShape, input_specs
from repro.distributed import param_sharding as PS
from repro.distributed import sharding as SH
from repro.distributed.sharding import make_mesh
from repro.models import model as M
from repro.roofline.analysis import extract_costs
from repro.training.trainer import make_train_state_abstract, make_train_step
import functools

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
batch_axes = ("pod", "data")
arch = os.environ["TEST_ARCH"]
cfg = reduced(ARCHS[arch])
shape = InputShape("t", 64, 8, os.environ["TEST_KIND"])
rules = SH.make_rules(multi_pod=True, fsdp=True, sp=(shape.kind == "train"))

with SH.use_rules(mesh, rules):
    if shape.kind == "train":
        state_abs = make_train_state_abstract(cfg)
        sh = PS.assign_param_shardings(state_abs, mesh=mesh, fsdp=True,
                                       batch_axes=batch_axes)
        batch_abs = input_specs(cfg, shape)
        bsh = PS.assign_batch_shardings(batch_abs, mesh=mesh,
                                        batch_axes=batch_axes)
        fn = jax.jit(make_train_step(cfg, raw=True),
                     in_shardings=(sh, bsh), donate_argnums=(0,))
        args = (state_abs, batch_abs)
    else:
        pools = 4
        params_abs = M.init_abstract(cfg)
        psh = PS.assign_param_shardings(params_abs, mesh=mesh, fsdp=True,
                                        batch_axes=batch_axes)
        cache_abs = M.make_cache_specs(cfg, max_seqs=8, num_pages=16,
                                       num_pools=pools)
        csh = PS.assign_cache_shardings(cache_abs, mesh=mesh,
                                        batch_axes=batch_axes)
        batch_abs = input_specs(cfg, shape, pages_per_seq=4)
        bsh = PS.assign_batch_shardings(batch_abs, mesh=mesh,
                                        batch_axes=batch_axes)
        apply = M.apply_prefill if shape.kind == "prefill" else M.apply_decode
        fn = jax.jit(functools.partial(apply, cfg, backend="xla"),
                     in_shardings=(psh, csh, bsh), donate_argnums=(1,))
        args = (params_abs, cache_abs, batch_abs)

    compiled = fn.lower(*args).compile()
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes >= 0
    flops, bytes_, colls = extract_costs(compiled)
    assert flops > 0 and bytes_ > 0
    print("OK", int(flops), sorted(colls))
"""


def _run(arch: str, kind: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["TEST_ARCH"] = arch
    env["TEST_KIND"] = kind
    out = subprocess.run([sys.executable, "-c", CODE], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-4000:]
    assert "OK" in out.stdout


@pytest.mark.parametrize("arch", [
    "smollm-135m", "deepseek-v2-236b", "zamba2-1.2b", "xlstm-350m",
])
@pytest.mark.parametrize("kind", ["train", "prefill", "decode"])
def test_cell_lowers_and_compiles_on_multipod_mesh(arch, kind):
    _run(arch, kind)
