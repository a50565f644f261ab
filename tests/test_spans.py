"""Names on the profiler's clock: the phase step's scopes and the host
loop's spans.

Every part of the compiled phase step runs under one ``jax.named_scope``
(``engine.batch`` / ``unpack`` / ``fwd_bwd`` / ``pack`` / ``update`` /
``average``), which reaches the compiled module as ``op_name`` metadata;
no op carries two, but for the mesh dispersion's ``engine.dispersion``,
nested in ``engine.update``. ``PhaseEngine.run`` marks each phase's host
loop with ``engine.next_block`` -> ``engine.dispatch`` -> ``engine.fetch`` ->
``engine.record`` spans on the driving thread, tagged with the phase's
first step. Neither changes what is computed: the trained state is
bit-identical with the scopes stripped and with a trace recording.
"""
import contextlib
import glob
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import AveragingSchedule, Compression, PhaseEngine
from repro.faults import FaultPlan
from repro.optim import SGD, Momentum

WORKERS, STEPS, K = 4, 12, 4
SCOPES = {"engine.batch", "engine.unpack", "engine.fwd_bwd", "engine.pack",
          "engine.update", "engine.average"}
HOST_LOOP = ["engine.next_block", "engine.dispatch", "engine.fetch",
             "engine.record"]
_SCOPE = re.compile(r"engine\.[a-z_]+")
# a sub-scope and the scope it runs inside
NESTED = {"engine.dispersion": "engine.update"}


def _loss(params, batch, rng):
    h = batch["x"] @ params["emb"].astype(jnp.float32).T
    r = (h.sum(-1) * params["w"].sum()
         + params["b"].astype(jnp.float32).sum() - batch["y"])
    return 0.5 * jnp.mean(r * r), {}


def _params():
    # mixed dtypes: the plane's unpack converts and slices, its pack
    # concatenates
    return {"emb": jnp.full((6, 4), 0.1, jnp.bfloat16),
            "w": jnp.linspace(0.0, 1.0, 4, dtype=jnp.float32),
            "b": jnp.zeros((3,), jnp.bfloat16)}


def _batches():
    rng = np.random.default_rng(0)
    return [{"x": jnp.asarray(rng.standard_normal((WORKERS, 8, 4)),
                              jnp.float32),
             "y": jnp.asarray(rng.standard_normal((WORKERS, 8)),
                              jnp.float32)}
            for _ in range(STEPS)]


def _engine(sched=None, **kw):
    return PhaseEngine(_loss, Momentum(lr=0.05, mu=0.9),
                       sched or AveragingSchedule("periodic", K), **kw)


def scopes_of(hlo_text: str) -> set:
    """The engine scopes named in a compiled module's ``op_name``
    metadata; fails if one op's name stack holds two of them, but for
    a sub-scope directly inside its own (``NESTED``; a fused op lists
    its parts' names joined by ``;``)."""
    found = set()
    for name in re.findall(r'op_name="([^"]*)"', hlo_text):
        for part in name.split(";"):
            s = set(_SCOPE.findall(part))
            for sub in s & NESTED.keys():
                assert f"{NESTED[sub]}/{sub}/" in part, part
            assert len(s - NESTED.keys()) <= 1, part
            found |= s
    return found


def _phase_text(eng):
    state, layout = eng.start_state(_params(), WORKERS, 0)
    stk = jax.tree.map(lambda *x: jnp.stack(x), *_batches()[:K])
    return PhaseEngine.run_phase.lower(eng, state, stk,
                                       layout=layout).compile().as_text()


@pytest.mark.parametrize("kw,want", [
    ({}, SCOPES),
    ({"fused_opt": False}, SCOPES),
    ({"flat": False}, SCOPES - {"engine.unpack", "engine.pack"}),
], ids=["flat_native", "flat", "tree"])
def test_compiled_phase_carries_every_scope(kw, want):
    assert scopes_of(_phase_text(_engine(**kw))) == want


def test_minibatch_event_is_scoped():
    # the flat-native minibatch event rides the update pass; the flat
    # carry runs it on its own, under engine.average
    fused = scopes_of(_phase_text(_engine(AveragingSchedule("minibatch"))))
    assert "engine.average" not in fused and "engine.update" in fused
    flat = scopes_of(_phase_text(_engine(AveragingSchedule("minibatch"),
                                         fused_opt=False)))
    assert "engine.average" in flat


SHARDED = r"""
import json, re, sys
import jax, jax.numpy as jnp
sys.path.insert(0, {tests!r})
from test_spans import (K, WORKERS, _batches, _engine, _params, scopes_of)
from repro.core import PhaseEngine
assert len(jax.devices()) == 4
mesh = jax.make_mesh((4,), ("data",))
eng = _engine(mesh=mesh)
state, _ = eng.start_state(_params(), WORKERS, 0)
stk = jax.tree.map(lambda *x: jnp.stack(x), *_batches()[:K])
txt = PhaseEngine.run_phase.lower(eng, state, stk).compile().as_text()
def scopes(op):
    return sorted({{s for m in re.finditer(
        op + r'[^\n]*op_name="([^"]*)"', txt)
        for s in re.findall(r"engine\.[a-z_]+", m.group(1))}})
print(json.dumps(dict(scopes=sorted(scopes_of(txt)),
                      psum_scopes=scopes("all-reduce"),
                      a2a_scopes=scopes("all-to-all"))))
"""


def test_sharded_phase_carries_every_scope():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    code = SHARDED.format(tests=os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    # a mesh carries the leaves: every scope but the plane's unpack and
    # pack, and the dispersion's inside the update
    assert set(res["scopes"]) == (SCOPES - {"engine.unpack", "engine.pack"}
                                  | {"engine.dispersion"})
    # the per-step dispersion's exchange and its scalar psum are the
    # update's, under engine.dispersion; the event gathers its mean; the
    # loss psum stays unscoped
    assert res["a2a_scopes"] == ["engine.dispersion", "engine.update"]
    assert "engine.dispersion" in res["psum_scopes"]


def _stream():
    # a generator: run() stages it through the Prefetcher thread
    yield from _batches()


def _events(trace_dir):
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("engine."):
                    st = dict(e.stats)
                    out.append((e.start_ns, e.name, i,
                                int(st["step"]) if "step" in st else None))
    return sorted(out)


def test_run_emits_host_spans_per_phase(tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        _engine().run(_params(), _stream(), num_workers=WORKERS, seed=1,
                      phase_len=K)
    evs = _events(str(tmp_path))
    names = [e[1] for e in evs]
    assert names[0] == "engine.start_state" and names[-1] == "engine.finish"
    loop = [e for e in evs if e[1] in HOST_LOOP]
    driving = {e[2] for e in loop}
    assert len(driving) == 1                    # one thread drives
    # each phase: next_block -> dispatch -> fetch -> record, tagged with
    # its first step; a last next_block finds the stream ended
    phases = [loop[i:i + 4] for i in range(0, len(loop) - 1, 4)]
    assert len(phases) == STEPS // K
    for n, ph in enumerate(phases):
        assert [e[1] for e in ph] == HOST_LOOP
        assert {e[3] for e in ph} == {n * K + 1}
    assert loop[-1][1] == "engine.next_block"
    # the Prefetcher's thread stages each block under engine.stage
    stage = [e for e in evs if e[1] == "engine.stage"]
    assert [e[3] for e in stage] == [1 + n * K for n in range(STEPS // K)]
    assert {e[2] for e in stage}.isdisjoint(driving)


def _final(eng, batches, **kw):
    _, _, st = eng.run(_params(), batches, num_workers=WORKERS, seed=5,
                       phase_len=K, return_state=True, **kw)
    return jax.tree.leaves(jax.device_get(st))


VARIANTS = {
    "flat_native": dict(),
    "flat_minibatch": dict(sched=AveragingSchedule("minibatch"),
                           fused_opt=False),
    "tree_hierarchical": dict(
        sched=AveragingSchedule("hierarchical", inner_phase_len=2,
                                outer_phase_len=4, inner_groups=2),
        flat=False),
    "faults_rejoin": dict(faults=FaultPlan.parse(
        "crash:m=1@t=3,rejoin:m=1@t=7", WORKERS)),
    "int8_wire": dict(compression=Compression("int8")),
}


@pytest.mark.parametrize("name", sorted(VARIANTS))
def test_scopes_and_spans_leave_the_state_bit_identical(name, tmp_path,
                                                         monkeypatch):
    kw = dict(VARIANTS[name])
    if name == "int8_wire":
        # SGD: the int8 event has no outer optimizer to refuse
        make = lambda: PhaseEngine(_loss, SGD(lr=0.05),
                                   AveragingSchedule("periodic", K), **kw)
    else:
        make = lambda: _engine(**kw)
    scoped = _final(make(), _stream())
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        traced = _final(make(), _stream())
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _final(make(), _stream())
    for a, b, c in zip(scoped, traced, bare):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
