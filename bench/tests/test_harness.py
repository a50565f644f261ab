"""The harness end to end on the CPU at a tiny size: the chip lookup is
skipped, everything else runs as on the chip. A sound program comes out
correct; with the timed path broken underneath, ``correct`` comes out
false, once for each fault a training cell can have."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from bench import run
from bench.tests.helpers import ROOT, tiny_root


def _run(root, seed=2 ** 31 + 7):
    return run.run_cell("tiny-cell", seed, 0.5, False, jax.devices(),
                        root=root, log=lambda *a: None)


@pytest.mark.parametrize("config", ["smollm-360m", "starcoder2-3b-l1"])
def test_sound_program_is_correct(tmp_path, config):
    res = _run(tiny_root(tmp_path, config=config))
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"tokens_per_s", "peak_hbm_gib", "setup_s"}
    assert list(res)[-1] == "checks"


def test_state_left_unchanged_is_caught(tmp_path, monkeypatch):
    import repro.core.engine as eng

    def frozen(plane, grads, planes, scalars, **kw):
        return plane, planes, jnp.float32(0.0)
    monkeypatch.setattr(eng, "opt_step_ref", frozen)
    res = _run(tiny_root(tmp_path))
    assert not res["correct"]
    assert res["checks"]["change_gap"]["value"] > res["checks"]["change_gap"]["limit"]


def test_half_batch_is_caught(tmp_path, monkeypatch):
    import repro.models as models
    full = models.lm_loss

    def half(cfg, params, batch, **kw):
        tok = batch["tokens"]
        return full(cfg, params, {"tokens": tok[: tok.shape[0] // 2]}, **kw)
    monkeypatch.setattr(models, "lm_loss", half)
    res = _run(tiny_root(tmp_path))
    assert not res["correct"], res["checks"]


SHARDED = r"""
import json, sys
sys.path[:0] = [{root!r}, {src!r}]
import jax
from bench import run
if {fault!r}:
    psum = jax.lax.psum
    # each chip's rows averaged among themselves only: the psum of a
    # local sum stands in for the sum over every chip
    jax.lax.psum = lambda x, axes, **kw: jax.tree.map(
        lambda v: v * psum(1, axes), x)
res = run.run_cell("tiny-cell", 11, 0.5, False, jax.devices(), root={tmp!r},
                   log=lambda *a: None)
print(json.dumps(res))
"""


@pytest.mark.parametrize("fault", [False, True], ids=["sound", "no_exchange"])
def test_sharded_cell(tmp_path, fault):
    tmp = tiny_root(tmp_path, traffic="m8x4c-b4-s128-k4", chips=4,
                    limits="smollm360m-m8x4c-s128-k4")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = SHARDED.format(root=ROOT, src=os.path.join(ROOT, "src"),
                          fault=fault, tmp=tmp)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["correct"] is (not fault), res["checks"]
