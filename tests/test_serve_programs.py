"""The serving entry's table of compiled step programs: a call whose key
(model config, batch, prompt length, cache length, mesh, and the globals of
the package's modules, so that a tuning constant or a function rebound
in-process misses) was served before lowers and compiles nothing, and gives
what a first call gives; each part of the key misses on its own."""
import dataclasses
import gc
import json
import os
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.launch import serve as serve_mod
from repro.models import moe
from repro.models import transformer as tf

ROOT = pathlib.Path(__file__).resolve().parents[1]
BUILD = {"serve.lower.prefill", "serve.compile.prefill",
         "serve.lower.decode", "serve.compile.decode"}
BASE = dict(reduced=True, batch=2, prompt_len=8, gen=4, seed=3)


@pytest.fixture(autouse=True)
def programs(monkeypatch):
    """A table of this test's own, so every first call here is a miss."""
    table = {}
    monkeypatch.setattr(serve_mod, "_PROGRAMS", table)
    return table


def _serve(**kw):
    return serve_mod.serve("starcoder2-3b", **{**BASE, **kw})


def _built(res) -> bool:
    """Whether the call lowered and compiled both programs (a miss)."""
    built = BUILD & res.spans.keys()
    assert built in (set(), BUILD), built
    if built:
        assert all(res.spans[name][0] == 1 for name in BUILD)
        assert res.compile_s > 0
    else:
        assert res.compile_s == 0
    return bool(built)


def _same(a, b):
    np.testing.assert_array_equal(a.prompt, b.prompt)
    np.testing.assert_array_equal(a.tokens, b.tokens)
    np.testing.assert_array_equal(np.asarray(a.logits), np.asarray(b.logits))


def test_a_second_call_with_the_same_shapes_builds_nothing_and_gives_the_same(programs):
    first = _serve()
    assert _built(first) and len(programs) == 1
    again = _serve()
    assert not _built(again) and len(programs) == 1
    assert {"serve.init", "serve.prefill", "serve.decode"} <= again.spans.keys()
    _same(first, again)


def test_a_reuse_makes_the_weights_from_its_own_seed():
    first = _serve(seed=3)
    other = _serve(seed=4)
    assert _built(first) and not _built(other)
    assert not np.array_equal(np.asarray(first.logits), np.asarray(other.logits))
    # the same seed on a fresh table: the reused programs baked in no weights
    serve_mod._PROGRAMS.clear()
    fresh = _serve(seed=4)
    assert _built(fresh)
    _same(other, fresh)


def _with_other_theta(serving_arch):
    def arch(*a, **k):
        arch = serving_arch(*a, **k)
        model = dataclasses.replace(arch.model, rope_theta=arch.model.rope_theta * 2)
        return dataclasses.replace(arch, model=model)
    return arch


@pytest.mark.parametrize("part", ["batch", "prompt_len", "gen", "layers",
                                  "model_config", "module_constant", "module_function"])
def test_each_key_part_misses_on_its_own(part):
    assert _built(_serve())
    kw = {"batch": {"batch": 3}, "prompt_len": {"prompt_len": 9}, "gen": {"gen": 5},
          "layers": {"layers": 1}}.get(part, {})
    arch = serve_mod.serving_arch
    group, mlp = moe.MOE_GROUP_SIZE, tf.mlp
    try:
        if part == "model_config":
            serve_mod.serving_arch = _with_other_theta(arch)
        if part == "module_constant":
            moe.MOE_GROUP_SIZE = group * 2
        if part == "module_function":
            tf.mlp = lambda *a, **k: mlp(*a, **k)
        assert _built(_serve(**kw)), part
        assert not _built(_serve(**kw)), part
    finally:
        serve_mod.serving_arch = arch
        moe.MOE_GROUP_SIZE, tf.mlp = group, mlp
    # the first key is still kept, and still hits
    assert not _built(_serve())


def test_a_layer_patched_between_calls_is_what_the_next_call_runs():
    first = _serve()
    mlp = tf.mlp
    try:
        tf.mlp = lambda *a, **k: 0 * mlp(*a, **k)
        patched = _serve()
    finally:
        tf.mlp = mlp
    assert _built(patched)
    assert not np.array_equal(np.asarray(first.logits), np.asarray(patched.logits))
    again = _serve()
    assert not _built(again)
    _same(first, again)


def test_the_table_drops_its_oldest_key_past_its_bound(programs, monkeypatch):
    monkeypatch.setattr(serve_mod, "_PROGRAMS_KEPT", 2)
    for batch in (1, 2, 1, 3):   # the hit on batch 1 does not keep it longer
        _serve(batch=batch)
    assert len(programs) == 2
    assert not _built(_serve(batch=3))
    assert _built(_serve(batch=1))


def test_the_table_holds_no_arrays():
    _serve(seed=3)
    gc.collect()
    live = len(jax.live_arrays())
    for seed in (4, 5):
        res = _serve(seed=seed)
        assert not _built(res)
        del res
        gc.collect()
        assert len(jax.live_arrays()) == live


_MESHES = r"""
import json
from repro.launch import serve as serve_mod
from repro.launch.mesh import make_device_mesh
kw = dict(reduced=True, batch=2, prompt_len=8, gen=4, seed=3)
built = []
for model in (1, 4, 4, 1):
    res = serve_mod.serve("starcoder2-3b", mesh=make_device_mesh(model), **kw)
    built.append([res.spans.get("serve.lower.prefill", (0, 0))[0],
                  res.spans.get("serve.lower.decode", (0, 0))[0], res.compile_s > 0])
print(json.dumps({"built": built, "kept": len(serve_mod._PROGRAMS)}))
"""


def test_a_four_device_mesh_misses_after_one_device():
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", _MESHES], env=env, capture_output=True,
                       text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    # the same shapes (max_seq 12 on both meshes): only the mesh differs
    assert out == {"built": [[1, 1, True], [1, 1, True], [0, 0, False], [0, 0, False]],
                   "kept": 2}
