"""Prefill attention runs in the Pallas flash kernel wherever each sequence
lives whole on one device (no mesh, or a mesh whose ``model`` axis, which
shards the sequence, has size 1), and keeps the XLA path where the mesh
splits the sequence."""
import dataclasses
import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.mesh import make_device_mesh, mesh_context
from repro.models import transformer as tf
from repro.models.attention import attention

ROOT = pathlib.Path(__file__).resolve().parents[1]
S = 200  # no multiple of the kernel's block: the wrapper pads it


def _cfg(dtype="float32"):
    return dataclasses.replace(get_config("starcoder2-3b").model.reduce(),
                               dtype=dtype)


def _prefill(cfg, params, tokens):
    return jax.jit(lambda p, t: tf.prefill(p, {"tokens": t}, cfg))(params, tokens)


def _dense(q, k, v, *, causal, window):
    return attention(q, k, v, causal=causal, window=window)


@pytest.mark.parametrize("dtype,atol", [("float32", 1e-4), ("bfloat16", 0.05)])
def test_prefill_through_the_kernel_matches_dense_attention(dtype, atol,
                                                            monkeypatch):
    cfg = _cfg(dtype)
    key = jax.random.key(5)
    params = tf.init_params(key, cfg)
    tokens = jax.random.randint(key, (2, S), 0, cfg.vocab_size)
    logits, caches = _prefill(cfg, params, tokens)
    monkeypatch.setattr(tf, "flash_attention", _dense)
    logits_d, caches_d = _prefill(cfg, params, tokens)
    f32 = lambda x: np.asarray(x, np.float32)  # noqa: E731
    assert np.all(np.isfinite(f32(logits)))
    np.testing.assert_allclose(f32(logits), f32(logits_d), atol=atol)
    for name in ("k", "v"):
        np.testing.assert_allclose(f32(caches[name]), f32(caches_d[name]),
                                   atol=atol)


def _prefill_text(mesh) -> str:
    cfg = _cfg()
    batch = {"tokens": jax.ShapeDtypeStruct((2, S), jnp.int32)}
    lower = jax.jit(lambda p, b: tf.prefill(p, b, cfg)).lower
    if mesh is None:
        return lower(tf.abstract_params(cfg), batch).as_text()
    with mesh_context(mesh):
        return lower(tf.abstract_params(cfg), batch).as_text()


@pytest.mark.parametrize("mesh", ["none", "one_device"])
def test_an_unsplit_sequence_prefills_in_the_kernel(mesh):
    text = _prefill_text(make_device_mesh() if mesh == "one_device" else None)
    assert "call @flash_attention" in text


_SPLIT = r"""
import sys
sys.path.insert(0, sys.argv[1])
from test_prefill_flash import _prefill_text
from repro.launch.mesh import make_device_mesh
print(int("flash_attention" in _prefill_text(make_device_mesh(4))))
"""


def test_a_sequence_split_over_model_keeps_the_xla_path():
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", _SPLIT, str(ROOT / "tests")],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    assert json.loads(r.stdout.strip().splitlines()[-1]) == 0
