"""The trace reduction and the readers built on it: exact on a small
made-up trace, and on a stretch of a recorded TPU v5e trace (a phase
boundary of smollm360m-m2-s128-k4, one whole update kernel in it)."""
import os
from types import SimpleNamespace

import pytest

from bench import flops, spec
from bench import trace as tr

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "v5e_smollm_m2_phase_boundary.json")

# a "while" holding two ops, then a gap, then one op; times in ns
MADE = [["%while.1 = f32[] while(x)", 100, 48],
        ["%fusion.2 = f32[8] fusion(y), kind=kLoop", 100, 20],
        ["%custom-call.3 = f32[2,64] custom-call(z), "
         'custom_call_target="tpu_custom_call"', 130, 15],
        ["%all-reduce-start.4 = f32[64] all-reduce-start(w)", 170, 10]]
HOST = [["consume", 150, 20, "python3"], ["stage", 140, 15, "worker"]]


def test_nesting_and_busy_on_a_made_up_trace():
    nested = {e[0][:9]: (s, leaf) for e, s, leaf in tr.nest(MADE)}
    assert nested["%while.1 "] == (13, False)     # 48 - 20 - 15
    assert nested["%fusion.2"] == (20, True)
    leaves = tr.leaves(MADE)
    assert len(leaves) == 3
    assert tr.busy_ns(leaves, 100, 200) == 45
    assert tr.gaps(leaves, 100, 200) == [(120, 130), (145, 170), (180, 200)]
    assert tr.op(MADE[2])[0] == "tpu_custom_call"
    assert tr.op(MADE[3])[2] == "all-reduce-start"
    top = tr.top_ops(MADE, 100, 200, n=2)
    assert top == [["fusion f32[8]", 20e-9], ["tpu_custom_call f32[2,64]", 15e-9]]
    gaps = tr.idle_gaps(leaves, HOST, 100, 200, n=2)
    assert gaps == [["python3: consume", 25e-9], ["no host event", 20e-9]]


def _ctx(trace, t0, t1, steps):
    c = spec.cell("smollm360m-m2-s128-k4")
    from bench.ref import decoder as ref
    return SimpleNamespace(
        traffic=c["traffic"], chips=1, width=ref.width(c["config"]),
        shape=ref.shape(c["config"]), peak=spec.peaks("TPU v5 lite"),
        tokens_per_step=2 * 4 * 128,
        trace=SimpleNamespace(events={0: tr.leaves(trace["devices"][0])},
                              t0=t0, t1=t1, window_s=(t1 - t0) / 1e9,
                              steps=steps, busy_s=None))


def test_recorded_v5e_stretch():
    t = tr.load_json(FIXTURE)
    t0, t1 = tr.span(t, "bench.window")
    leaves = tr.leaves(t["devices"][0])
    assert tr.busy_ns(leaves, t0, t1) == 86_344_794
    assert t1 - t0 == 89_921_474
    # the gap between two phases: the host fetches the phase's results
    lab, sec = tr.idle_gaps(leaves, t["host"], t0, t1, n=1)[0]
    assert lab.endswith("consume") and sec == pytest.approx(2.921474e-3)
    top = tr.top_ops(t["devices"][0], t0, t1, n=1)[0]
    assert top[0].startswith("tpu_custom_call (f32[2,361821120]")
    assert top[1] == pytest.approx(0.027030382)
    ctx = _ctx(t, t0, t1, steps=1)
    assert ctx.width == 361_821_120
    assert spec.reader("collective_ms")(ctx) is None   # one chip
    ctx.trace.busy_s = [86_344_794 / 1e9]
    assert spec.reader("device_idle_frac")(ctx) == pytest.approx(
        100 * (1 - 86_344_794 / 89_921_474))


def test_flop_and_byte_counts():
    c = spec.cell("smollm360m-m2-s128-k4")["config"]
    from bench.ref import decoder as ref
    s = ref.shape(c)
    # 49152*960 (tied head) + 32 * (960*(960+2*320) + 960*960 + 3*960*2560)
    assert flops.matmul_params(s) == 361_758_720
    assert flops.mean_context(4, 0) == 2.5
    assert flops.mean_context(6, 2) == (1 + 2 + 2 + 2 + 2 + 2) / 6
    assert flops.train_flops_per_token(s, 128) == pytest.approx(
        6 * 361_758_720 + 32 * 6 * 2 * 960 * 64.5)


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        spec.peaks("cpu")
