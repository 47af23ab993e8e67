"""Sharded lowering tests — run in a subprocess with 8 fake devices so the
main pytest process keeps its single real device (the dryrun.py contract)."""
import json
import pathlib
import subprocess
import sys

import pytest

# one multi-minute XLA compile in the module fixture dominates tier-1 wall
# clock on small containers
pytestmark = pytest.mark.slow

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, dataclasses
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.launch.mesh import make_test_mesh, mesh_context
from repro.launch.step import (input_specs, abstract_params, abstract_opt_state,
                               make_shardings, build_train_step, build_serve_step,
                               abstract_caches)
from repro.launch.analysis import parse_collectives

out = {}
mesh = make_test_mesh((2, 4), ("data", "model"))
for name in ("qwen2-7b", "rwkv6-3b", "mixtral-8x22b"):
    arch = get_config(name)
    arch = dataclasses.replace(arch, model=arch.model.reduce())
    shape = ShapeConfig("t", seq_len=32, global_batch=4, kind="train")
    with mesh_context(mesh):
        psh, osh, bsh, _ = make_shardings(arch, shape, mesh)
        step = build_train_step(arch, shape, mesh)
        comp = jax.jit(step,
                       in_shardings=(psh, osh, bsh, NamedSharding(mesh, P())),
                       out_shardings=(psh, osh, None),
                       donate_argnums=(0, 1)).lower(
            abstract_params(arch), abstract_opt_state(arch),
            input_specs(arch, shape), jax.ShapeDtypeStruct((), jnp.int32)
        ).compile()
    colls = parse_collectives(comp.as_text())
    out[name] = {
        "compiled": True,
        "collective_ops": sum(colls.counts.values()),
        "has_all_reduce": colls.counts.get("all-reduce", 0) > 0,
    }
    # decode too
    shape_d = ShapeConfig("d", seq_len=64, global_batch=4, kind="decode")
    with mesh_context(mesh):
        psh, _, bsh, csh = make_shardings(arch, shape_d, mesh)
        sstep = build_serve_step(arch)
        comp = jax.jit(sstep,
                       in_shardings=(psh, bsh, csh, NamedSharding(mesh, P())),
                       out_shardings=(None, None, csh), donate_argnums=(2,)).lower(
            abstract_params(arch), input_specs(arch, shape_d),
            abstract_caches(arch, shape_d), jax.ShapeDtypeStruct((), jnp.int32)
        ).compile()
    out[name]["decode_compiled"] = True
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def subproc_result():
    repo = pathlib.Path(__file__).resolve().parents[1]
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, timeout=600,
        cwd=repo, env={"PYTHONPATH": str(repo / "src"), "PATH": "/usr/bin:/bin"},
    )
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_sharded_train_compiles(subproc_result):
    for name, rec in subproc_result.items():
        assert rec["compiled"], name


def test_sharded_decode_compiles(subproc_result):
    for name, rec in subproc_result.items():
        assert rec["decode_compiled"], name


def test_data_parallel_gradient_sync_present(subproc_result):
    """Training on a (data, model) mesh must synchronize gradients."""
    for name, rec in subproc_result.items():
        assert rec["has_all_reduce"], name
