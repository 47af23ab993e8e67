"""Compile rehearsal for a TPU v5e that is described, not attached.

The TPU compiler refuses what interpret mode accepts: unaligned kernel
slices, too much VMEM, programs larger than HBM. These tests compile the
main serving path at published widths for v5e chips, with no chip and no
run: the attention kernels at starcoder2-3b head shapes, starcoder2-3b's
prefill and decode steps on one chip, and nemotron-4-15b's decode sharded
over a (data=1, model=4) 2x2 mesh.

The topology is described inside the module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file. Keep these tests in this one file for the same reason.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, SingleDeviceSharding

from repro.configs import ShapeConfig
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.paged_attention.kernel import paged_attention_pallas
from repro.launch.dryrun import lower_cell
from repro.launch.serve import serving_arch

HBM_BYTES = 16 * 2**30  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _mesh(devices, model: int) -> Mesh:
    return Mesh(np.asarray(devices[:model]).reshape(1, model),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)


def _device_bytes(compiled) -> int:
    mem = compiled.memory_analysis()
    return mem.argument_size_in_bytes + mem.temp_size_in_bytes


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_flash_attention_compiles_for_v5e(topo):
    chip = SingleDeviceSharding(topo.devices[0])
    cfg = serving_arch("starcoder2-3b").model
    q = _sds((1, 2048, cfg.num_heads, cfg.head_dim), jnp.bfloat16, chip)
    kv = _sds((1, 2048, cfg.num_kv_heads, cfg.head_dim), jnp.bfloat16, chip)
    fn = jax.jit(lambda q, k, v: flash_attention_pallas(q, k, v,
                                                        interpret=False))
    compiled = fn.lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_attention_compiles_for_v5e(topo):
    chip = SingleDeviceSharding(topo.devices[0])
    cfg = serving_arch("starcoder2-3b").model
    b, pages, page = 8, 1024, 16
    q = _sds((b, cfg.num_heads, cfg.head_dim), jnp.bfloat16, chip)
    pool = _sds((pages, page, cfg.num_kv_heads, cfg.head_dim), jnp.bfloat16,
                chip)
    table = _sds((b, pages // b), jnp.int32, chip)
    lens = _sds((b,), jnp.int32, chip)
    fn = jax.jit(lambda *a: paged_attention_pallas(*a, interpret=False))
    compiled = fn.lower(q, pool, pool, table, lens).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kind,seq_len", [("prefill", 512), ("decode", 2048)])
def test_starcoder2_3b_step_fits_one_v5e(topo, kind, seq_len):
    arch = serving_arch("starcoder2-3b")
    shape = ShapeConfig(kind, seq_len=seq_len, global_batch=8, kind=kind)
    _, compiled = lower_cell(arch, shape, _mesh(topo.devices, 1), None)
    assert 0 < _device_bytes(compiled) < HBM_BYTES


def test_nemotron_4_15b_decode_shards_over_four_v5e(topo):
    arch = serving_arch("nemotron-4-15b")
    shape = ShapeConfig("decode", seq_len=544, global_batch=8, kind="decode")
    _, compiled = lower_cell(arch, shape, _mesh(topo.devices, 4), None)
    per_device = _device_bytes(compiled)
    # 29 GiB of bf16 weights cannot sit on one chip; a quarter share can
    assert arch.model.total_params() * 2 > HBM_BYTES
    assert 0 < per_device < HBM_BYTES
    assert "all-reduce" in compiled.as_text()
