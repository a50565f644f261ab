"""Telemetry plane: enabling it must never change training.

The load-bearing invariant (docs/TELEMETRY.md): ``telemetry=True``
threads a metrics accumulator through the phase scan carry and flushes
it with the phase's existing trace fetch — so telemetry ON vs OFF is
bit-identical in the final EngineState across every engine path, every
schedule, compression, faults, checkpoint/resume, and the mesh phase
(subprocess), and adds ZERO extra host syncs (the
device_get count per run is unchanged). On top of that: the metrics
themselves must agree with the independently recorded history, the
JSONL schema round-trips (with future-version refusal), ``RunLog``
reconstructs the legacy hist dict key for key, and the report CLI
renders a phase table.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import AveragingSchedule, Compression, PhaseEngine
from repro.elastic import ElasticPlan, run_elastic
from repro.faults import FaultPlan
from repro.optim import Momentum
from repro.telemetry import (JsonlSink, MemorySink, NullSink, RunLog,
                             TELEMETRY_VERSION, init_history, make_record,
                             parse_record, run_meta_record)
from repro.telemetry.report import render
from repro.telemetry.timing import time_run, timed
from repro.topology import Topology, comm_bytes

WORKERS, STEPS, DIM, SAMPLES = 4, 40, 12, 256


def _problem(seed=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((SAMPLES, DIM))
    y = X @ rng.standard_normal(DIM)
    idx = rng.integers(0, SAMPLES, (STEPS, WORKERS, 8))
    Xj, yj = jnp.asarray(X), jnp.asarray(y)
    return lambda: [{"x": Xj[idx[t]], "y": yj[idx[t]]}
                    for t in range(STEPS)]


def _loss(params, batch, rng):
    r = batch["x"] @ params["w"] - batch["y"]
    return 0.5 * jnp.mean(r * r), {}


def _params():
    return {"w": jnp.zeros(DIM)}


SCHEDULES = {
    "oneshot": AveragingSchedule("oneshot"),
    "minibatch": AveragingSchedule("minibatch"),
    "periodic": AveragingSchedule("periodic", 8),
    "stochastic": AveragingSchedule("stochastic", zeta=0.2),
    "hierarchical": AveragingSchedule("hierarchical", inner_phase_len=5,
                                      outer_phase_len=20, inner_groups=2),
    "adaptive_threshold": AveragingSchedule("adaptive_threshold",
                                            disp_threshold=0.05,
                                            disp_ema_beta=0.5),
    "adaptive_budget": AveragingSchedule("adaptive_budget", comm_budget=6,
                                         budget_horizon=STEPS),
}


def _pair(sch, **kw):
    """(telemetry-off, telemetry-on) engines, otherwise identical."""
    off = PhaseEngine(_loss, Momentum(lr=0.05, mu=0.9), sch, **kw)
    on = PhaseEngine(_loss, Momentum(lr=0.05, mu=0.9), sch,
                     telemetry=True, **kw)
    return off, on


def _assert_state_identical(s_off, s_on):
    la, lb = jax.tree.leaves(s_off), jax.tree.leaves(s_on)
    assert len(la) == len(lb)
    for a, b in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _run_both(off, on, sink=None, batches=None, **kw):
    batches = batches or _problem()
    kw.setdefault("num_workers", WORKERS)
    kw.setdefault("seed", 3)
    kw.setdefault("record_every", 1)
    f0, h0, s0 = off.run(_params(), batches(), return_state=True, **kw)
    f1, h1, s1 = on.run(_params(), batches(), return_state=True,
                        sink=sink, **kw)
    _assert_state_identical(s0, s1)
    np.testing.assert_array_equal(np.asarray(f0["w"]), np.asarray(f1["w"]))
    assert h0 == h1
    return h1


# ------------------------------------------------------------- invariance

@pytest.mark.parametrize("name", list(SCHEDULES))
def test_invariant_across_schedules(name):
    off, on = _pair(SCHEDULES[name])
    _run_both(off, on, sink=MemorySink())


@pytest.mark.parametrize("path,kw", [
    ("flat", {"fused_opt": False}),
    ("tree", {"flat": False}),
    ("host", {}),
], ids=["flat", "tree", "host"])
def test_invariant_across_paths(path, kw):
    if path == "host":
        # run_host never carries the accumulator; its engine flag must
        # still be inert
        off, on = _pair(SCHEDULES["periodic"])
        f0, h0 = off.run_host(_params(), _problem()(),
                              num_workers=WORKERS, seed=3, record_every=1)
        f1, h1 = on.run_host(_params(), _problem()(),
                             num_workers=WORKERS, seed=3, record_every=1)
        np.testing.assert_array_equal(np.asarray(f0["w"]),
                                      np.asarray(f1["w"]))
        assert h0 == h1
    else:
        off, on = _pair(SCHEDULES["periodic"], **kw)
        _run_both(off, on, sink=MemorySink())


def test_invariant_with_compression_and_topology():
    off, on = _pair(SCHEDULES["periodic"],
                    compression=Compression("int8"),
                    topology=Topology.build("ring", WORKERS))
    _run_both(off, on, sink=MemorySink())


def test_invariant_with_faults():
    plan = FaultPlan.parse("crash:m=2@t=10,rejoin:m=2@t=25", WORKERS,
                           straggle_prob=0.25)
    off, on = _pair(SCHEDULES["periodic"], faults=plan)
    sink = MemorySink()
    _run_both(off, on, sink=sink)
    fe = [(r["kind"], r["worker"], r["step"]) for r in sink.records
          if r["type"] == "fault_event"]
    assert fe == [("crash", 2, 10), ("rejoin", 2, 25)]
    pm = [r for r in sink.records if r["type"] == "phase_metrics"]
    # the crash window (steps 11..25) has 3 alive workers
    assert min(r["alive_min"] for r in pm) == 3.0
    assert any(r["straggle_rate"] > 0 for r in pm)


def test_invariant_across_resume():
    """Telemetry never touches the checkpoint: a resumed telemetry run
    matches the uninterrupted telemetry-off run bit-for-bit, and the
    resumed phases flush fresh accumulators."""
    from repro.checkpoint import load_engine_state, save_engine_state
    import tempfile
    batches = _problem()
    off, on = _pair(SCHEDULES["stochastic"])
    f_full, h_full, s_full = off.run(
        _params(), batches(), num_workers=WORKERS, seed=7,
        record_every=8, return_state=True)
    cut = 24
    sink = MemorySink()
    _, h1, st = on.run(_params(), batches()[:cut], num_workers=WORKERS,
                       seed=7, record_every=8, return_state=True,
                       sink=sink)
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "ck")
        save_engine_state(path, st)
        loaded, at = load_engine_state(path, on.init(_params(), WORKERS, 7))
    assert at == cut
    f_res, h2, s_res = on.run(None, batches()[cut:], num_workers=WORKERS,
                              record_every=8, state=loaded,
                              return_state=True, sink=sink)
    _assert_state_identical(s_full, s_res)
    np.testing.assert_array_equal(np.asarray(f_full["w"]),
                                  np.asarray(f_res["w"]))
    assert h_full["loss"] == h1["loss"] + h2["loss"]
    pm = [r for r in sink.records if r["type"] == "phase_metrics"]
    assert sum(r["steps"] for r in pm) == STEPS
    # phase windows are contiguous across the resume cut
    spans = [(r["t0"], r["t1"]) for r in pm]
    assert spans[0][0] == 1 and spans[-1][1] == STEPS
    assert all(a2 == b1 + 1 for (_, b1), (a2, _) in zip(spans, spans[1:]))


def test_no_extra_host_syncs(monkeypatch):
    """One device_get per phase, telemetry on or off — the metrics ride
    the existing trace fetch instead of adding their own."""
    counts = []
    real = jax.device_get

    def counting(x):
        counts.append(1)
        return real(x)

    off, on = _pair(SCHEDULES["periodic"])
    monkeypatch.setattr(jax, "device_get", counting)
    off.run(_params(), _problem()(), num_workers=WORKERS, seed=3,
            phase_len=10)
    n_off = len(counts)
    counts.clear()
    on.run(_params(), _problem()(), num_workers=WORKERS, seed=3,
           phase_len=10, sink=MemorySink())
    n_on = len(counts)
    assert n_on == n_off == STEPS // 10


# ------------------------------------------------- metrics vs history

def test_metrics_match_history():
    off, on = _pair(SCHEDULES["periodic"])
    sink = MemorySink()
    hist = _run_both(off, on, sink=sink, phase_len=10)
    pm = [r for r in sink.records if r["type"] == "phase_metrics"]
    assert [r["steps"] for r in pm] == [10] * 4
    assert sum(r["events"] for r in pm) == hist["averages"]
    losses = [v for _, v in hist["loss"]]
    disps = [v for _, v in hist["disp_trace"]]
    for i, r in enumerate(pm):
        seg_l, seg_d = losses[i * 10:(i + 1) * 10], disps[i * 10:(i + 1) * 10]
        np.testing.assert_allclose(r["loss_mean"], np.mean(seg_l),
                                   rtol=1e-5)
        np.testing.assert_allclose(r["loss_max"], np.max(seg_l), rtol=1e-6)
        np.testing.assert_allclose(r["disp_max"], np.max(seg_d), rtol=1e-5)
    # nominal wire bytes = events x topology.comm_bytes pricing
    per_event = comm_bytes(Topology.full(WORKERS), 1, DIM, "f32")
    assert sum(r["comm_bytes"] for r in pm) == hist["averages"] * per_event


def test_metrics_price_compressed_wire():
    off, on = _pair(SCHEDULES["periodic"], compression=Compression("int8"))
    sink = MemorySink()
    hist = _run_both(off, on, sink=sink)
    per_event = comm_bytes(Topology.full(WORKERS), 1, DIM, "int8")
    total = sum(r["comm_bytes"] for r in sink.records
                if r["type"] == "phase_metrics")
    assert total == hist["averages"] * per_event


# ---------------------------------------------------- schema + RunLog

def test_record_schema_round_trip(tmp_path):
    records = [
        run_meta_record(config={"workers": 4}),
        make_record("phase_metrics", t0=1, t1=10, steps=10, events=1),
        make_record("averaging_event", step=8, dispersion=0.1, scope="all"),
        make_record("fault_event", step=3, kind="crash", worker=1),
        make_record("resize_event", step=5, old_m=4, new_m=6),
        make_record("checkpoint_event", step=10, path="ck.state",
                    layout_version=5),
    ]
    path = tmp_path / "run.jsonl"
    with JsonlSink(path) as sink:
        for r in records:
            sink.emit(r)
    log = RunLog.load(path)
    assert [r["type"] for r in log.records] == [r["type"] for r in records]
    for orig, back in zip(records, log.records):
        assert orig == back
    assert all(r["v"] == TELEMETRY_VERSION for r in log.records)


def test_reader_refuses_future_version_and_unknown_type():
    with pytest.raises(ValueError, match="newer than this reader"):
        parse_record({"v": TELEMETRY_VERSION + 1, "type": "run_meta"})
    with pytest.raises(ValueError, match="unknown telemetry record type"):
        parse_record({"v": TELEMETRY_VERSION, "type": "mystery"})
    with pytest.raises(ValueError, match="no integer 'v'"):
        parse_record({"type": "run_meta"})
    with pytest.raises(ValueError, match="unknown telemetry record type"):
        make_record("mystery")
    # MemorySink validates on emit
    with pytest.raises(ValueError):
        MemorySink().emit({"type": "run_meta"})
    NullSink().emit({"anything": "goes-nowhere"})


def test_runlog_history_matches_engine_hist(tmp_path):
    off, on = _pair(SCHEDULES["stochastic"])
    path = tmp_path / "run.jsonl"
    with JsonlSink(path) as sink:
        hist = _run_both(off, on, sink=sink)
    rebuilt = RunLog.load(path).history()
    assert rebuilt["loss"] == hist["loss"]
    assert rebuilt["disp_trace"] == hist["disp_trace"]
    assert rebuilt["dispersion"] == hist["dispersion"]
    assert rebuilt["averages"] == hist["averages"]
    assert rebuilt["eval"] == [] and rebuilt["worker_eval"] == []


def test_init_history_is_the_shared_constructor():
    hist = init_history()
    assert hist == {"loss": [], "dispersion": [], "disp_trace": [],
                    "averages": 0, "eval": [], "worker_eval": []}
    assert init_history(resizes=True)["resizes"] == []
    # fresh lists every call — a shared-mutable constructor would let
    # one run's history leak into the next
    a, b = init_history(), init_history()
    a["loss"].append((1, 0.0))
    assert b["loss"] == []


# ------------------------------------------------------------- elastic

def test_elastic_emits_resize_events():
    rng = np.random.default_rng(0)
    X = rng.standard_normal((SAMPLES, DIM))
    y = X @ rng.standard_normal(DIM)
    Xj, yj = jnp.asarray(X), jnp.asarray(y)

    def factory(m, t0, k):
        g = np.random.default_rng(1000 + t0)
        idx = g.integers(0, SAMPLES, (k, m, 8))
        return [{"x": Xj[idx[t]], "y": yj[idx[t]]} for t in range(k)]

    plan = ElasticPlan.parse(WORKERS, grow_at=("21:6",))
    off, on = _pair(AveragingSchedule("periodic", 5))
    f0, h0 = run_elastic(off, _params(), factory, plan, steps=STEPS,
                         seed=3, record_every=1)
    sink = MemorySink()
    f1, h1 = run_elastic(on, _params(), factory, plan, steps=STEPS,
                         seed=3, record_every=1, sink=sink)
    np.testing.assert_array_equal(np.asarray(f0["w"]), np.asarray(f1["w"]))
    assert h0 == h1
    rz = [r for r in sink.records if r["type"] == "resize_event"]
    assert [(r["step"], r["old_m"], r["new_m"]) for r in rz] == [(21, 4, 6)]
    assert RunLog(sink.records).history()["resizes"] == h1["resizes"]
    # phase_metrics keep flowing across the resize
    assert sum(r["steps"] for r in sink.records
               if r["type"] == "phase_metrics") == STEPS


def test_sink_requires_telemetry_engine():
    off, _ = _pair(SCHEDULES["periodic"])
    with pytest.raises(ValueError, match="telemetry=True"):
        off.run(_params(), _problem()(), num_workers=WORKERS,
                sink=MemorySink())


# ------------------------------------------------------------- sharded

_SHARDED_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.core import AveragingSchedule, PhaseEngine
from repro.optim import Momentum
from repro.telemetry import MemorySink

assert len(jax.devices()) == 8, jax.devices()
DIM, SAMPLES, WORKERS, STEPS = 12, 256, 16, 41
rng = np.random.default_rng(0)
X = rng.standard_normal((SAMPLES, DIM))
y = X @ rng.standard_normal(DIM)
Xj, yj = jnp.asarray(X), jnp.asarray(y)
idx = rng.integers(0, SAMPLES, (STEPS, WORKERS, 8))

def loss_fn(params, batch, rng):
    r = batch["x"] @ params["w"] - batch["y"]
    return 0.5 * jnp.mean(r * r), {}

params = {"w": jnp.zeros(DIM)}
batches = lambda: [{"x": Xj[idx[t]], "y": yj[idx[t]]} for t in range(STEPS)]
mesh = jax.make_mesh((8,), ("data",))
sch = AveragingSchedule("periodic", 8)
kw = dict(num_workers=WORKERS, seed=3, record_every=1, phase_len=16)
off = PhaseEngine(loss_fn, Momentum(lr=0.05, mu=0.9), sch, mesh=mesh)
on = PhaseEngine(loss_fn, Momentum(lr=0.05, mu=0.9), sch, mesh=mesh,
                 telemetry=True)
f0, h0, s0 = off.run(params, batches(), return_state=True, **kw)
sink = MemorySink()
f1, h1, s1 = on.run(params, batches(), return_state=True, sink=sink, **kw)
for a, b in zip(jax.tree.leaves(s0), jax.tree.leaves(s1)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
assert h0 == h1
pm = [r for r in sink.records if r["type"] == "phase_metrics"]
assert sum(r["steps"] for r in pm) == STEPS
assert sum(r["events"] for r in pm) == h1["averages"]
assert all(r["alive_mean"] == WORKERS for r in pm)
assert all(r["carry"] == "leaf" for r in pm)
print("ALL-OK")
"""


def test_sharded_telemetry_invariant():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    out = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    assert "ALL-OK" in out.stdout


# -------------------------------------------------------------- timing

def test_timed_and_time_run():
    calls = []

    def fn():
        calls.append(1)

    assert timed(fn) >= 0.0
    calls.clear()
    ms = time_run(fn, steps=10, reps=3, warmup=2)
    assert ms >= 0.0
    assert len(calls) == 5  # 2 warmup + 3 timed
    with pytest.raises(ValueError):
        time_run(fn, steps=0)
    with pytest.raises(ValueError):
        time_run(fn, steps=1, reps=0)


def test_time_run_blocks_device_output():
    x = jnp.arange(8.0)
    f = jax.jit(lambda v: v * 2)
    assert time_run(lambda: f(x), steps=1, block=True) >= 0.0


def test_profile_trace_noop_without_dir():
    from repro.telemetry.timing import profile_trace
    with profile_trace(None):
        pass
    with profile_trace(""):
        pass


# -------------------------------------------------------------- report

def test_report_renders_phase_table(tmp_path):
    _, on = _pair(SCHEDULES["periodic"])
    path = tmp_path / "run.jsonl"
    with JsonlSink(path) as sink:
        sink.emit(run_meta_record(config={
            "workers": WORKERS, "lr": 0.05, "momentum": 0.9,
            "avg": "periodic", "phase_len": 8}))
        on.run(_params(), _problem()(), num_workers=WORKERS, seed=3,
               record_every=1, phase_len=10, sink=sink)
    text = render(RunLog.load(path))
    assert "disp_mean" in text and "B/event" in text
    assert f"total: {STEPS} steps" in text
    # the variance-model prediction column calibrates from the recipe
    assert "disp_pred" in text
    lines = [ln for ln in text.splitlines() if ln.strip().startswith("0 ")]
    assert lines, text


def test_report_cli(tmp_path, capsys):
    from repro.telemetry.report import main
    path = tmp_path / "run.jsonl"
    with JsonlSink(path) as sink:
        sink.emit(make_record("phase_metrics", t0=1, t1=10, steps=10,
                              events=2, comm_bytes=96.0, loss_mean=1.0,
                              disp_mean=0.1, disp_max=0.2,
                              alive_mean=4.0, straggle_rate=0.0,
                              wall_s=0.5))
    assert main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "total: 10 steps, 2 events" in out
