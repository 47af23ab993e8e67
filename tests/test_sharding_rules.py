"""The one sharding rule (DESIGN.md §6): tensor parallel over "model",
FSDP over "data". Parameter specs come from a parameter's path and rank
alone, and the optimizer state takes its parameter's spec."""
import dataclasses

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.launch.mesh import make_device_mesh
from repro.launch.sharding import param_specs
from repro.launch.step import abstract_opt_state, abstract_params, make_shardings

ARCHS = ["starcoder2-3b", "nemotron-4-15b"]


def _reduced(name):
    arch = get_config(name)
    return dataclasses.replace(arch, model=arch.model.reduce())


def _is_spec(x):
    return isinstance(x, P)


@pytest.mark.parametrize("name", ARCHS)
def test_param_specs_shard_heads_and_ffn_over_model_and_the_other_dim_over_data(name):
    arch = _reduced(name)
    params = abstract_params(arch)
    specs = param_specs(arch.model, params)
    layer = specs["layers"]
    # stacked layers: the leading L dim is never sharded
    for proj in ("wq", "wk", "wv"):
        assert layer["attn"][proj] == P(None, "data", "model"), proj
    assert layer["attn"]["wo"] == P(None, "model", "data")
    assert layer["mlp"]["w_up"] == P(None, "data", "model")
    assert layer["mlp"]["w_down"] == P(None, "model", "data")
    # vocab-parallel embedding (and untied head), norms replicated
    assert specs["embedding"] == P("model", "data")
    if not arch.model.tie_embeddings:
        assert specs["lm_head"] == P("model", "data")
    assert specs["final_norm"]["scale"] == P()
    assert layer["ln1"]["scale"] == P(None)
    # every spec names at most the leaf's rank, each axis at most once
    flat_specs = jax.tree.leaves(specs, is_leaf=_is_spec)
    flat_params = jax.tree.leaves(params)
    assert len(flat_specs) == len(flat_params)
    for spec, leaf in zip(flat_specs, flat_params):
        assert len(spec) <= len(leaf.shape)
        named = [e for e in spec if e is not None]
        assert len(named) == len(set(named))


def test_optimizer_state_takes_its_parameters_spec():
    arch = _reduced("starcoder2-3b")
    shape = ShapeConfig("t", seq_len=16, global_batch=2, kind="train")
    params_sh, opt_sh, _, _ = make_shardings(arch, shape, make_device_mesh())
    opt = abstract_opt_state(arch)
    pairs = zip(jax.tree.leaves(params_sh),
                jax.tree.leaves(opt_sh["leaves"],
                                is_leaf=lambda x: isinstance(x, dict) and "master" in x))
    for param_sh, moments in pairs:
        assert moments.keys() >= {"master", "m", "v"}
        for name in ("master", "m", "v"):
            # a spec shorter than the rank leaves the rest unsharded
            rank = len(moments[name].spec)
            want = tuple(param_sh.spec) + (None,) * (rank - len(param_sh.spec))
            assert tuple(moments[name].spec) == want, name
    assert opt_sh["step"].spec == P()
    assert len(jax.tree.leaves(opt_sh)) == len(jax.tree.leaves(opt))
