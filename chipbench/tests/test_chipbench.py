"""CPU tests of the benchmark: the harness as data, the counts, the trace
reduction, the reference against the served model, the control, and runs
with the timed path broken underneath.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q
"""
from __future__ import annotations

import dataclasses
import json
import os
import re
import shutil
import subprocess
import sys
import textwrap
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from chipbench import check, counts, harness, spec, traffic
from chipbench import trace as trace_lib
from chipbench.references import dense_gqa

ROOT = spec.ROOT
BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")

# the reduced starcoder2-3b that ``serve(reduced=True)`` serves, as a file
# would state it (float32, so program and reference agree to rounding)
SMALL = {"num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 1, "head_dim": 16, "intermediate_size": 128,
         "vocab_size": 128, "dtype": "float32"}
SMALL_MIX = {"loop": "closed", "batch": 4, "prompt_len": 16, "gen": 8}
# largest logit_rms a float32 program may read at this size; the float8
# control and every planted fault that alters logits read above it
SMALL_LIMIT = 1e-3


def small_cell(name="sc2_3b.codegen", **over) -> spec.Cell:
    cell = spec.resolve(name)
    cfg = {**cell.config, **SMALL, **over}
    return dataclasses.replace(cell, config=cfg, traffic=dict(SMALL_MIX),
                               limits=dict(cell.limits, logit_rms=SMALL_LIMIT))


@pytest.fixture
def no_cache(monkeypatch):
    """Keep CPU test compiles out of the checkout's compile cache."""
    import repro.launch.compile_cache as cc
    monkeypatch.setattr(cc, "enable_compile_cache", lambda *a, **k: "")


def cpu_run(cell, seed=2**33 + 5, trace=False):
    return harness.run(cell, seed, 0.0, trace, t_start=time.perf_counter(),
                       require_tpu=False, serve_kwargs={"reduced": True})


# --- the harness is data ---------------------------------------------------

@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves_its_files(name):
    cell = spec.resolve(name)
    assert cell.chips == cell.config["mesh"]["data"] * cell.config["mesh"]["model"]
    assert {"batch", "prompt_len", "gen", "loop"} <= set(cell.traffic)
    assert cell.limits["check_requests"] > 0 and cell.limits["logit_rms"] > 0
    for m in cell.end_to_end + cell.per_layer:
        assert callable(spec.reader(m["name"]))
    assert callable(spec.reference(cell.config).logits)


def test_a_cell_added_as_data_only_is_picked_up(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests", "testdata"))
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "sc2_3b.tiny", "config": "starcoder2-3b",
                               "traffic": "tiny", "chips": 1, "why": "data only"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "chipbench/traffic/tiny.json").write_text(json.dumps(SMALL_MIX))
    (tmp_path / "chipbench/limits/sc2_3b.tiny.json").write_text(
        json.dumps({"check_requests": 2, "logit_rms": 0.5}))
    cell = spec.resolve("sc2_3b.tiny", root=tmp_path)
    assert cell.traffic["batch"] == SMALL_MIX["batch"]
    assert cell.config["arch"] == "starcoder2-3b"
    assert ({m["name"] for m in cell.per_layer}
            == {m["name"] for m in BENCH["per_layer"] if "workloads" not in m})
    assert spec.reader("tokens_per_s", root=tmp_path) is not None


def test_names_units_and_moves_keep_to_the_contract():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        reporting = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m.get("workloads", cells)) <= reporting
        assert m["source"] in ("device_trace", "program_span", "program_counter",
                               "host_clock")
        assert "\n" not in m["layer"] and len(m["layer"]) <= 200
    layers = {}
    for m in BENCH["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and NAME.match(w["config"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for c in BENCH["configs"]:
        assert NAME.match(c["name"])
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
        assert all(NAME.match(k) for k in c["reduced"])


def test_without_a_tpu_the_command_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, *BENCH["command"][1:], "--workload",
                        "sc2_3b.codegen", "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
    assert "platform cpu" in p.stderr


def test_without_the_program_the_command_fails(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload", "sc2_3b.codegen",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and '"correct"' not in p.stdout


def test_job_seeds_are_fixed_by_the_run_seed_and_fit_in_31_bits():
    mix = spec.resolve("sc2_3b.codegen").traffic
    big = 2**31 + 12345
    a = [traffic.job(mix, big, traffic.WINDOW, i) for i in range(3)]
    assert a == [traffic.job(mix, big, traffic.WINDOW, i) for i in range(3)]
    assert len({j.seed for j in a}) == 3 and all(0 <= j.seed < 2**31 for j in a)
    assert traffic.job(mix, big, traffic.WARMUP).seed not in {j.seed for j in a}
    assert {(j.batch, j.prompt_len, j.gen) for j in a} == {(32, 256, 256)}
    # the rows the check may compare are drawn before each job runs
    rows = [check.job_rows(big, i, 32, 2) for i in range(3)]
    assert rows == [check.job_rows(big, i, 32, 2) for i in range(3)]
    assert all(len(set(r)) == 2 and 0 <= min(r) and max(r) < 32 for r in rows)


# --- counts ------------------------------------------------------------------

def test_counts_match_hand_reckonings_for_starcoder2_3b():
    cfg = spec.resolve("sc2_3b.codegen").config
    # 30 layers x (2*3072^2 + 2*3072*256 + 2*3072*12288 + 4*3072) + 49152*3072 + 2*3072
    assert counts.params(cfg) == 30 * 95_956_992 + 150_994_944 + 6144 == 3_029_710_848
    assert counts.weight_bytes(cfg) == 6_059_421_696
    assert counts.kv_bytes_per_token(cfg) == 30 * 2 * 2 * 128 * 2
    # B 32 x 512-token prompts: 2 * 16384 * 30 * 95.9e6 matmul flops, causal
    # attention 30 * 2 * 512^2 * 3072 per row, last-position logits
    want = 32 * (2 * 512 * 30 * 95_944_704 + 30 * 2 * 512**2 * 3072 + 2 * 150_994_944)
    assert counts.prefill_flops(cfg, 32, 512) == want
    assert 95.0e12 < want < 96.5e12
    live = counts.decode_live(512, 256)
    assert live == 512 + 1 + 127
    # weights once (6.06 GB less the norms) + live KV + new KV + bf16 logits
    want_b = (30 * 95_944_704 * 2 + 150_994_944 * 2 + 32 * 640 * 30720
              + 32 * 30720 + 32 * 49152 * 2)
    assert counts.decode_step_bytes(cfg, 32, live) == want_b
    t, bound = counts.decode_bound(cfg, 32, live, 1, {"bf16_flops": 197e12,
                                                      "hbm_bytes_per_s": 819e9})
    assert bound == "bytes" and 8.1e-3 < t < 8.2e-3


# --- trace reduction ---------------------------------------------------------

def test_union_and_gaps_on_hand_made_spans():
    S = trace_lib.Span
    dev = trace_lib.DeviceTrace(0, [], [S("a", 0, 10), S("b", 5, 20), S("c", 30, 40)])
    tr = trace_lib.Trace([dev], [S("host.x", 18, 35), S("host.y", 0, 100)], (0, 50))
    assert tr.busy_s(dev) == pytest.approx(30e-9)
    assert trace_lib.idle_gaps(tr, dev) == [["host.x", 10e-9], ["host.y", 10e-9]]


# --- the reference -----------------------------------------------------------

def _file_cfg(model_cfg) -> dict:
    return {"num_hidden_layers": model_cfg.num_layers, "hidden_size": model_cfg.d_model,
            "num_attention_heads": model_cfg.num_heads,
            "num_key_value_heads": model_cfg.num_kv_heads, "head_dim": model_cfg.head_dim,
            "intermediate_size": model_cfg.d_ff, "vocab_size": model_cfg.vocab_size,
            "hidden_act": harness.PROGRAM_ACT.get(model_cfg.activation, model_cfg.activation),
            "norm": model_cfg.norm, "norm_eps": 1e-5, "rope_theta": model_cfg.rope_theta,
            "dtype": model_cfg.dtype, "tie_word_embeddings": model_cfg.tie_embeddings}


@pytest.mark.parametrize("act", ["gelu", "squared_relu"])
def test_reference_remakes_the_served_weights_bit_for_bit(act):
    from repro.configs.base import ModelConfig
    from repro.models import init_params
    mc = ModelConfig(name="t", family="dense", num_layers=3, d_model=64, num_heads=4,
                     num_kv_heads=2, head_dim=16, d_ff=96, vocab_size=300, activation=act,
                     norm="layernorm", dtype="bfloat16", tie_embeddings=False)
    p = init_params(jax.random.key(77), mc)
    cfg = _file_cfg(mc)
    items = dense_gqa._cfg_items(cfg)
    k_emb, layer_keys, k_head = dense_gqa._keys(77, cfg)
    for layer, lk in enumerate(layer_keys):
        w = dense_gqa._layer_weights(lk, items)
        for group, names in (("attn", ("wq", "wk", "wv", "wo")), ("mlp", ("w_up", "w_down"))):
            for k in names:
                np.testing.assert_array_equal(np.asarray(p["layers"][group][k][layer]),
                                              np.asarray(w[k]))
    np.testing.assert_array_equal(np.asarray(p["embedding"]),
                                  np.asarray(dense_gqa._table(k_emb, items)))
    np.testing.assert_array_equal(np.asarray(p["lm_head"]),
                                  np.asarray(dense_gqa._table(k_head, items)))


def _serve_vs_reference(arch: str, mesh_model: int) -> tuple[float, float, float]:
    """(max |program - reference| logit, widest served gap, widest fp8 gap)."""
    from repro.launch.mesh import make_device_mesh
    from repro.launch.serve import serve, serving_arch
    cfg = _file_cfg(serving_arch(arch, reduced=True).model)
    res = serve(arch, reduced=True, batch=4, prompt_len=16, gen=8, seed=11,
                mesh=make_device_mesh(mesh_model))
    seqs, first = check.teacher_forced(res.prompt, res.tokens)
    ref = dense_gqa.logits(cfg, 11, seqs, first)
    low = dense_gqa.logits(cfg, 11, seqs, first, quant="fp8")
    diff = float(np.abs(np.asarray(res.logits, np.float32)[..., :cfg["vocab_size"]] - ref).max())
    return (diff, float(check.gaps(ref, res.tokens).max()),
            float(check.gaps(ref, low.argmax(-1)).max()))


@pytest.mark.parametrize("arch", ["starcoder2-3b", "nemotron-4-15b"])
def test_reference_agrees_with_prefill_and_decode_unsharded(arch):
    diff, served, control = _serve_vs_reference(arch, 1)
    assert diff < 1e-4 and served <= SMALL_LIMIT
    # the float8 control ranks some token first that the reference does not
    assert control > 3 * SMALL_LIMIT


def test_reference_agrees_with_prefill_and_decode_on_four_devices():
    code = textwrap.dedent(f"""
        import json, sys
        sys.path[:0] = [{str(ROOT)!r}, {str(ROOT / 'src')!r}]
        sys.path.insert(0, {str(Path(__file__).parent)!r})
        from test_chipbench import _serve_vs_reference
        print(json.dumps(_serve_vs_reference("nemotron-4-15b", 4)))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    diff, served, control = json.loads(p.stdout.strip().splitlines()[-1])
    assert diff < 1e-4 and served <= SMALL_LIMIT and control > 3 * SMALL_LIMIT


# --- whole runs on the CPU, sound and with the timed path broken --------------

def test_a_sound_run_is_correct(no_cache):
    res = cpu_run(small_cell())
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] == SMALL_MIX["batch"] and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {m["name"] for m in BENCH["end_to_end"]}


def test_a_traced_run_whose_metric_reads_nothing_fails(no_cache):
    """The CPU's trace has no TPU plane, so the device-trace metrics read
    nothing: the run fails and prints no result instead of leaving them out."""
    with pytest.raises(harness.MissingMetric):
        cpu_run(small_cell(), trace=True)


def test_the_control_through_the_harness_is_not_correct(no_cache):
    from chipbench import calibrate
    recs = calibrate.readings(small_cell(), [3], [3], require_tpu=False,
                              serve_kwargs={"reduced": True})
    sound, control = recs
    assert not sound["control"] and sound["correct"] is True
    assert control["control"] and control["correct"] is False
    assert control["logit_rms"] > 3 * SMALL_LIMIT


def _state_unchanged(serve_mod):
    orig = serve_mod.build_serve_step

    def build(arch, **kw):
        step = orig(arch, **kw)
        return lambda p, b, caches, n: step(p, b, caches, n)[:2] + (caches,)
    return "build_serve_step", build


def _token_altered(serve_mod):
    orig = serve_mod.build_serve_step

    def build(arch, **kw):
        step = orig(arch, **kw)

        def faulty(p, b, caches, n):
            tok, logits, caches = step(p, b, caches, n)
            return (tok + 1) % arch.model.vocab_size, logits, caches
        return faulty
    return "build_serve_step", build


def _prompt_cache_dropped(serve_mod):
    return "_fill_caches", lambda caches, prompt_caches, cfg: caches


@pytest.mark.parametrize("fault, caught_by", [(_state_unchanged, "logit_rms"),
                                              (_token_altered, "tokens_not_argmax"),
                                              (_prompt_cache_dropped, "logit_rms")])
def test_a_run_with_the_timed_path_broken_is_not_correct(fault, caught_by, monkeypatch,
                                                          no_cache):
    import repro.launch.serve as serve_mod
    monkeypatch.setattr(serve_mod, *fault(serve_mod))
    res = cpu_run(small_cell())
    assert res["correct"] is False
    check_ = res["checks"][caught_by]
    assert check_["value"] > 10 * max(check_["limit"], SMALL_LIMIT)


def test_a_four_device_run_without_the_exchange_is_not_correct():
    """The feed-forward block's all-reduce over the model axis left out:
    each chip keeps its own partial sum."""
    code = textwrap.dedent(f"""
        import dataclasses, json, sys, time
        sys.path[:0] = [{str(ROOT)!r}, {str(ROOT / 'src')!r}]
        sys.path.insert(0, {str(Path(__file__).parent)!r})
        import jax
        from jax.sharding import PartitionSpec as P
        import repro.launch.compile_cache as cc
        import repro.models.transformer as tf
        from repro.models.common import ACTIVATIONS
        from test_chipbench import small_cell, cpu_run
        cc.enable_compile_cache = lambda *a, **k: ""
        cell = small_cell(mesh={{"data": 1, "model": 4}})
        sound = cpu_run(cell)

        def local_mlp(params, x, activation):
            act = ACTIVATIONS[activation]
            return jax.shard_map(lambda x, wu, wd: act(x @ wu) @ wd,
                                 in_specs=(P(), P(None, "model"), P("model", None)),
                                 out_specs=P(), check_vma=False)(
                x, params["w_up"], params["w_down"])
        tf.mlp = local_mlp
        broken = cpu_run(cell)
        print(json.dumps([sound["correct"], broken["correct"],
                          broken["checks"]["logit_rms"]["value"]]))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    sound, broken, gap = json.loads(p.stdout.strip().splitlines()[-1])
    assert sound is True and broken is False and gap > 10 * SMALL_LIMIT
