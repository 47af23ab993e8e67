"""The kernels' one backend check, and where the compile cache is placed."""
import os
import pathlib
import subprocess
import sys

import jax
import pytest

from repro.kernels.backend import use_interpret
from repro.launch import compile_cache

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("platform,interpret", [("cpu", True), ("tpu", False)])
def test_use_interpret_by_platform(platform, interpret):
    assert use_interpret(platform) is interpret


@pytest.mark.parametrize("platform", ["gpu", "cuda", "rocm", "METAL"])
def test_use_interpret_refuses_other_backends(platform):
    with pytest.raises(RuntimeError, match=platform):
        use_interpret(platform)


def test_use_interpret_reads_the_default_backend():
    assert use_interpret() is (jax.default_backend() == "cpu")


def test_checkout_root_holds_the_repo():
    assert (compile_cache.CHECKOUT_ROOT / "chip_smoke.py").is_file()
    assert (compile_cache.CHECKOUT_ROOT / "src" / "repro").is_dir()


def test_env_dir_wins_and_sets_nothing(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / "env"))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache(tmp_path) == str(tmp_path / "env")
    assert jax.config.jax_compilation_cache_dir == before


def test_fixed_dir_when_env_unset(monkeypatch, tmp_path):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache(tmp_path)
        assert path == str(tmp_path / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        # the default is the checkout, never a temp dir, pid or time
        assert compile_cache.enable_compile_cache() == str(
            compile_cache.CHECKOUT_ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


_COMPILE = """
import sys, pathlib
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
print(enable_compile_cache(pathlib.Path(sys.argv[1])))
jax.jit(lambda x: jnp.tanh(x) * 3)(jnp.ones(8)).block_until_ready()
"""


@pytest.mark.parametrize("env_set", [True, False])
def test_cache_entries_land_in_the_chosen_dir(tmp_path, env_set):
    root, env_dir = tmp_path / "checkout", tmp_path / "env_cache"
    env = {k: v for k, v in os.environ.items() if k != compile_cache.ENV_VAR}
    env.update(PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
    if env_set:
        env[compile_cache.ENV_VAR] = str(env_dir)
    r = subprocess.run([sys.executable, "-c", _COMPILE, str(root)], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    used = env_dir if env_set else root / ".jax_cache"
    unused = root / ".jax_cache" if env_set else env_dir
    assert r.stdout.strip().splitlines()[-1] == str(used)
    assert any(used.iterdir())
    assert not unused.exists()
