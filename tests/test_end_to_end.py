"""End-to-end integration: loss goes down training a reduced model through
the full driver (checkpoint/restart + UM-prefetched pipeline), and the
serve driver generates tokens."""
import json
import os
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.launch.mesh import make_device_mesh
from repro.launch.serve import serve
from repro.launch.train import train


def test_train_loss_decreases(tmp_path):
    state, report = train("starcoder2-3b", steps=30, batch=4, seq=64,
                          ckpt_dir=str(tmp_path), checkpoint_every=10)
    assert report.steps_completed == 30
    first = np.mean(report.losses[:5])
    last = np.mean(report.losses[-5:])
    assert last < first - 0.05, (first, last)


def test_train_with_fault_injection_recovers(tmp_path):
    state, report = train("qwen2-7b", steps=25, batch=4, seq=64,
                          ckpt_dir=str(tmp_path), checkpoint_every=5,
                          fault_schedule=(12,))
    assert report.restarts == 1
    assert report.steps_completed >= 25


@pytest.mark.parametrize("arch", ["qwen2-7b", "rwkv6-3b", "mixtral-8x22b",
                                  "musicgen-medium"])
def test_serve_generates(arch):
    toks = serve(arch, reduced=True, batch=2, prompt_len=16, gen=6).tokens
    assert toks.shape[0] == 2 and toks.shape[1] == 6
    assert np.all(toks >= 0)


_SHARDED_SERVE = r"""
import json
import numpy as np
from repro.launch.mesh import make_device_mesh
from repro.launch.serve import serve
kw = dict(reduced=True, batch=4, prompt_len=16, gen=6)
one = serve("nemotron-4-15b", **kw)
four = serve("nemotron-4-15b", mesh=make_device_mesh(4), **kw)
print(json.dumps({
    "tokens_equal": bool((one.tokens == four.tokens).all()),
    "max_logit_diff": float(np.abs(np.asarray(one.logits)
                                   - np.asarray(four.logits)).max()),
    "logits_spec": str(four.logits.sharding.spec),
}))
"""


def test_serve_sharded_over_four_devices_matches_one():
    """The model=4 serving path (params and caches created sharded) gives
    the unsharded tokens; float32, reduced config, four CPU devices."""
    repo = pathlib.Path(__file__).resolve().parents[1]
    env = dict(os.environ, PYTHONPATH=str(repo / "src"), JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", _SHARDED_SERVE], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["tokens_equal"]
    assert out["max_logit_diff"] < 1e-4
    assert "model" in out["logits_spec"]


def test_device_mesh_needs_enough_devices():
    assert make_device_mesh().shape == {"data": 1, "model": 1}
    with pytest.raises(ValueError, match="devices"):
        make_device_mesh(len(jax.devices()) + 1)
