"""Nemotron-4-15B as published: partial rotary positions, served through the
normal path and compared with the benchmark's plain float32 reference
(``chipbench/references/nemotron4.py``) on seeded weights, at the reduced
size on the CPU."""
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

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import check, harness  # noqa: E402
from chipbench.references import dense_gqa, nemotron4  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.launch import serve as serve_mod  # noqa: E402
from repro.launch.mesh import make_device_mesh  # noqa: E402
from repro.models.common import apply_rope, apply_rotary, rope_angles  # noqa: E402

CONFIG_FILE = ROOT / "chipbench" / "configs" / "nemotron-4-15b.json"
# float32 program against float32 reference: rounding only
TOL = 1e-4


def _qk(dh=16, heads=(4, 2), seed=0):
    kq, kk = jax.random.split(jax.random.key(seed))
    q = jax.random.normal(kq, (2, 5, heads[0], dh), jnp.float32)
    k = jax.random.normal(kk, (2, 5, heads[1], dh), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(3, 8)[None], (2, 5))
    return q, k, pos


def test_partial_rotary_turns_the_leading_dims_only():
    q, k, pos = _qk()
    rq, rk = apply_rope(q, k, pos, 10_000.0, 8)
    for x, r in ((q, rq), (k, rk)):
        np.testing.assert_array_equal(np.asarray(r[..., 8:]), np.asarray(x[..., 8:]))
        cos, sin = rope_angles(pos, 8, 10_000.0)
        np.testing.assert_array_equal(np.asarray(r[..., :8]),
                                      np.asarray(apply_rotary(x[..., :8], cos, sin)))
        assert not np.allclose(np.asarray(r[..., :8]), np.asarray(x[..., :8]))


def test_full_rotary_is_the_whole_head_rope_unchanged():
    q, k, pos = _qk()
    cos, sin = rope_angles(pos, 16, 10_000.0)
    want = (apply_rotary(q, cos, sin), apply_rotary(k, cos, sin))
    for got in (apply_rope(q, k, pos, 10_000.0), apply_rope(q, k, pos, 10_000.0, 16)):
        for g, w in zip(got, want, strict=True):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_rotary_dims_stay_even_when_reduced():
    model = get_config("nemotron-4-15b").model
    assert (model.head_dim, model.rotary_dim) == (128, 64)
    assert model.reduce().rotary_dim == 8
    with pytest.raises(ValueError, match="rotary"):
        dataclasses.replace(model, partial_rotary_factor=0.2)  # 25 dims


def _file_cfg(model) -> dict:
    """The configuration file with the reduced model's sizes in it."""
    cfg = json.loads(CONFIG_FILE.read_text())
    return {**cfg, "num_hidden_layers": model.num_layers, "hidden_size": model.d_model,
            "num_attention_heads": model.num_heads,
            "num_key_value_heads": model.num_kv_heads, "head_dim": model.head_dim,
            "intermediate_size": model.d_ff, "vocab_size": model.vocab_size,
            "dtype": model.dtype, "partial_rotary_factor": model.partial_rotary_factor}


def served_vs_reference(mesh_model: int = 1, factor: float | None = None) -> dict:
    """Max |served - reference| logit over the prefill's first token and each
    cached decode step, teacher-forced, against both references.
    ``factor`` serves the model with another partial_rotary_factor."""
    arch = serve_mod.serving_arch("nemotron-4-15b", reduced=True)
    cfg = _file_cfg(arch.model)
    orig = serve_mod.serving_arch
    if factor is not None:
        model = dataclasses.replace(arch.model, partial_rotary_factor=factor)
        serve_mod.serving_arch = lambda *a, **k: dataclasses.replace(arch, model=model)
    try:
        res = serve_mod.serve("nemotron-4-15b", reduced=True, batch=4, prompt_len=16, gen=8,
                              seed=11, mesh=make_device_mesh(mesh_model))
    finally:
        serve_mod.serving_arch = orig
    seqs, first = check.teacher_forced(res.prompt, res.tokens)
    got = np.asarray(res.logits, np.float32)[..., :cfg["vocab_size"]]
    return {name: float(np.abs(got - ref.logits(cfg, 11, seqs, first)).max())
            for name, ref in (("nemotron4", nemotron4), ("dense_gqa", dense_gqa))}


def test_served_model_matches_its_reference():
    diff = served_vs_reference()
    assert diff["nemotron4"] < TOL
    # whole-head rotary is another model: the comparison tells them apart
    assert diff["dense_gqa"] > 100 * TOL


def test_served_with_whole_head_rotary_fails_the_reference():
    diff = served_vs_reference(factor=1.0)
    assert diff["nemotron4"] > 100 * TOL
    assert diff["dense_gqa"] < TOL


_FOUR = r"""
import json, sys
sys.path.insert(0, {tests!r})
from test_nemotron4 import served_vs_reference
print(json.dumps(served_vs_reference(4)))
"""


def test_served_over_four_devices_matches_its_reference():
    env = dict(os.environ, PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]),
               JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", _FOUR.format(tests=str(ROOT / "tests"))],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    diff = json.loads(r.stdout.strip().splitlines()[-1])
    assert diff["nemotron4"] < TOL and diff["dense_gqa"] > 100 * TOL


def test_config_file_states_the_served_model():
    cfg = json.loads(CONFIG_FILE.read_text())
    model = serve_mod.serving_arch(cfg["arch"]).model
    assert harness.config_mismatches(model, cfg) == []
    # keys the harness does not compare
    assert model.partial_rotary_factor == cfg["partial_rotary_factor"]
    assert model.rotary_dim == int(cfg["partial_rotary_factor"] * cfg["head_dim"])
    assert (model.num_layers, model.d_model, model.vocab_size) == (32, 6144, 256_000)
    assert cfg["reduced"] == [] and cfg["mesh"] == {"data": 1, "model": 4}
