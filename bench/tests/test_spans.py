"""The readers of the program's scopes and spans (``bench.spans``): exact
on a small made-up trace, and read from a real profiler trace of
``PhaseEngine.run`` on the CPU (host spans only: the CPU has no TPU op
line)."""
import os
import re
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import spec, spans
from bench import trace as tr

BODY = "jit(run_phase)/while/body/closed_call/"
# a "while" holding one op of each scope, an op fused across two scopes,
# one with no op_name that counts under the scope of its consumer, and
# one in no scope: [text, start, dur, own scope, scope]; times in ns
OPS = [["%while.1 = (f32[2,8]) while(x)", 100, 100, "", ""],
       ["%fusion.2 = u32[2] fusion(k)", 100, 10, "engine.batch",
        "engine.batch"],
       ["%fusion.3 = bf16[8] fusion(p)", 110, 20, "engine.unpack",
        "engine.unpack"],
       ["%convolution.4 = f32[8,8] convolution(a, b)", 130, 30,
        "engine.fwd_bwd", "engine.fwd_bwd"],
       ["%dynamic-update-slice.5 = bf16[2,8] dynamic-update-slice(g)", 160,
        6, "", "engine.pack"],
       ["%fusion.6 = f32[2,8] fusion(g)", 166, 4, "engine.pack",
        "engine.pack"],
       ['%custom-call.7 = f32[2,8] custom-call(p), '
        'custom_call_target="tpu_custom_call"', 170, 15, "engine.update",
        "engine.update"],
       ["%fusion.8 = f32[8] fusion(p)", 185, 5, "engine.average",
        "engine.average"],
       ["%fusion.9 = f32[8] fusion(p)", 190, 4,
        "mixed:engine.average+engine.update",
        "mixed:engine.average+engine.update"],
       ["%dynamic-update-slice.10 = f32[4] dynamic-update-slice(l)", 194, 3,
        "", ""]]
# two phases (first steps 5 and 9) and the start of a third; the stager
# on a thread of its own
SPANS = [["engine.next_block", 10, 2, 5, "python3#1"],
         ["engine.dispatch", 12, 3, 5, "python3#1"],
         ["engine.stage", 20, 6, 9, "python3#2"],
         ["engine.fetch", 15, 80, 5, "python3#1"],
         ["engine.record", 95, 2, 5, "python3#1"],
         ["engine.next_block", 97, 1, 9, "python3#1"],
         ["engine.dispatch", 98, 4, 9, "python3#1"],
         ["engine.fetch", 102, 80, 9, "python3#1"],
         ["engine.record", 182, 2, 9, "python3#1"],
         ["engine.next_block", 184, 3, 13, "python3#1"],
         ["engine.dispatch", 187, 5, 13, "python3#1"]]
MADE = {"ops": {0: OPS}, "spans": SPANS}
READERS = ("fwd_bwd_ms", "avg_event_ms", "host_bubble_ms", "input_wait_ms")


@pytest.mark.parametrize("name,want", [
    (BODY + "vmap(engine.fwd_bwd)/transpose(jvp())/dot_general",
     "engine.fwd_bwd"),
    (BODY + "transpose(jvp(engine.fwd_bwd))/mul", "engine.fwd_bwd"),
    (BODY + "engine.average/cond/branch_2_fun/reduce_sum", "engine.average"),
    ("a/engine.update/mul;b/engine.update/sub", "engine.update"),
    ("a/engine.update/mul;b/vmap(engine.pack)/sub",
     "mixed:engine.pack+engine.update"),
    ("jit(run_phase)/while/body/my_engine.update/add", ""),
    ("jit(run_phase)/while/body/dynamic_update_slice", ""),
    ("", ""),
])
def test_scope_of_an_op_name(name, want):
    assert spans.scope(name) == want


def test_scope_time_on_a_made_up_trace():
    got = spans.scope_ns(OPS, 100, 200)
    assert got == {"engine.batch": 10, "engine.unpack": 20,
                   "engine.fwd_bwd": 30, "engine.pack": 10,
                   "engine.update": 15, "engine.average": 5,
                   "mixed:engine.average+engine.update": 4, "": 3,
                   "named": 88, "inferred:engine.pack": 6, "busy": 97}
    # clipped to the window
    assert spans.scope_ns(OPS, 140, 165)["engine.fwd_bwd"] == 20


def test_host_gaps_on_a_made_up_trace():
    # end of fetch 5 (95) to end of dispatch 9 (102); 182 to 192
    assert spans.bubbles_ns(SPANS, 0, 200) == [7, 10]
    assert spans.waits_ns(SPANS, 0, 200) == [2, 1, 3]
    # a span must end inside the window; a wait must lie in it whole
    assert spans.bubbles_ns(SPANS, 96, 200) == [10]
    assert spans.waits_ns(SPANS, 50, 200) == [1, 3]
    assert spans.bubbles_ns(SPANS, 0, 150) == [7]


def _ctx(names, t0, t1, steps):
    return SimpleNamespace(
        workload="smollm360m-m2-s128-k4", chips=1,
        trace=SimpleNamespace(t0=t0, t1=t1, steps=steps, names=names))


def test_readers_on_a_made_up_trace(capsys):
    ctx = _ctx(MADE, 100, 200, 1)
    read = {n: spec.reader(n) for n in READERS}
    assert spans.scoped_ms(ctx, "plumbing", ("engine.unpack", "engine.pack")
                           ) == pytest.approx(30e-6)
    assert read["fwd_bwd_ms"](ctx) == pytest.approx(30e-6)
    assert read["avg_event_ms"](ctx) == pytest.approx(5e-6)
    # host spans: the boundary 9 -> 13 ends inside [100, 200]
    assert read["host_bubble_ms"](ctx) == pytest.approx(10e-6)
    assert read["input_wait_ms"](ctx) == pytest.approx(3e-6)
    err = capsys.readouterr().err
    assert "over 1 phase boundaries" in err and "over 1 phases" in err
    # beside each device metric, the part the rule placed: the
    # dynamic-update-slice with no op_name, counted under pack
    split = {m[0]: (float(m[1]), float(m[2])) for m in re.findall(
        r"\] (\w+): (\S+) ms per step, (\S+) of it", err)}
    assert split["plumbing"] == pytest.approx((30e-6, 6e-6))
    assert split["fwd_bwd_ms"] == pytest.approx((30e-6, 0.0))
    # per step: two steps halve the device readings
    ctx.trace.steps = 2
    assert read["fwd_bwd_ms"](ctx) == pytest.approx(15e-6)


def test_readers_find_nothing_in_a_trace_without_names():
    """The parent program has no scopes and no spans: every reader
    returns None and none raises."""
    bare = {"ops": {0: [e[:3] + ["", ""] for e in OPS]}, "spans": []}
    for names in (bare, None):
        ctx = _ctx(names, 100, 200, 1)
        assert [spec.reader(n)(ctx) for n in READERS] == [None] * 4
    ctx.trace = None
    assert [spec.reader(n)(ctx) for n in READERS] == [None] * 4


def test_spans_of_a_profiled_run_on_the_cpu(tmp_path):
    """``load`` reads the spans ``PhaseEngine.run`` writes into a real
    profiler trace, with their steps and threads."""
    from repro.core import AveragingSchedule, PhaseEngine
    from repro.optim import Momentum

    def loss(params, batch, rng):
        r = batch["x"] @ params["w"] - batch["y"]
        return 0.5 * jnp.mean(r * r), {}

    rng = np.random.default_rng(0)
    stream = ({"x": jnp.asarray(rng.standard_normal((2, 4, 3)), jnp.float32),
               "y": jnp.zeros((2, 4), jnp.float32)} for _ in range(12))
    eng = PhaseEngine(loss, Momentum(lr=0.1, mu=0.9),
                      AveragingSchedule("periodic", 4))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        eng.run({"w": jnp.zeros(3)}, stream, num_workers=2, phase_len=4)
    t = spans.load(str(tmp_path))
    assert t["ops"] == {}                      # no TPU op line on a CPU
    sp = t["spans"]
    steps = sorted(s[3] for s in sp if s[0] == "engine.dispatch")
    assert steps == [1, 5, 9]
    assert len({s[4] for s in sp if s[0] == "engine.stage"}) == 1
    lo = min(s[1] for s in sp)
    hi = max(s[1] + s[2] for s in sp)
    assert len(spans.bubbles_ns(sp, lo, hi)) == 2
    assert len(spans.waits_ns(sp, lo, hi)) == 4    # the last finds the end
    assert all(b > 0 for b in spans.bubbles_ns(sp, lo, hi))
    path = str(tmp_path / "spans.json")
    spans.save(t, path)
    assert spans.load_json(path) == t


# --------------------------------------------------------------------------
# the compiled module: a small HloProto and XSpace in protobuf wire format
# --------------------------------------------------------------------------

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields):
    """A message from (field number, value): an int is a varint, bytes
    or str length-delimited, a list of ints packed."""
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
        else:
            if isinstance(v, str):
                v = v.encode()
            elif isinstance(v, list):
                v = b"".join(_varint(x) for x in v)
            out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return out


def _ins(name, iid, op_name="", operands=(), called=()):
    meta = _msg((2, op_name)) if op_name else b""
    return _msg((1, name), (2, "op"), (7, meta), (35, iid),
                (36, list(operands)), (38, list(called)))


UNPACK, FWD = BODY + "vmap(engine.unpack)/convert", BODY + "vmap(engine.fwd_bwd)/dot"
PACK, UPDATE = BODY + "vmap(engine.pack)/concatenate", BODY + "engine.update/add"
# the phase body (computation 1) and a loop XLA built (computation 2)
HLO = _msg((1, _msg(
    (1, "jit_run_phase"),
    (3, _msg((1, "body"), (5, 1),
             (2, _ins("param", 10)),
             (2, _ins("convert", 11, "", [10])),     # feeds 2 unpack, 1 fwd
             (2, _ins("u1", 12, UNPACK, [11])),
             (2, _ins("u2", 13, UNPACK, [11])),
             (2, _ins("f1", 14, FWD, [12, 13, 11])),
             (2, _ins("dus1", 15, "", [14])),        # a chain into pack
             (2, _ins("dus2", 16, "", [15])),
             (2, _ins("k", 17, PACK, [16])),
             (2, _ins("loop", 18, "", [11], [2])),   # feeds unpack
             (2, _ins("u3", 19, UNPACK, [18])),
             (2, _ins("root", 20, "", [17, 19])),    # fed by pack, unpack
             (2, _ins("lone", 21)),
             (2, _ins("mixed", 22, UPDATE + ";" + PACK, [17])))),
    (3, _msg((1, "loop_body"), (5, 2),
             (2, _ins("q", 30)),
             (2, _ins("x", 31, "", [30])),
             (2, _ins("y", 32, UPDATE, [31])),
             (2, _ins("z", 33, "", [30])))))))


def test_scopes_of_a_compiled_module():
    got = spans.hlo_scopes(HLO)
    un, fw, pk, up = ("engine.unpack", "engine.fwd_bwd", "engine.pack",
                      "engine.update")
    mixed = "mixed:engine.pack+engine.update"
    assert got == {
        "param": ("", un), "convert": ("", un), "u1": (un, un),
        "u2": (un, un), "f1": (fw, fw), "dus1": ("", pk), "dus2": ("", pk),
        "k": (pk, pk), "loop": ("", un), "u3": (un, un),
        "root": ("", un),        # a tie between its producers: the earlier
        "lone": ("", ""), "mixed": (mixed, mixed),
        "q": ("", up), "x": ("", up), "y": (up, up),
        "z": ("", un)}           # nothing named near: its loop's scope


def _plane(name, *fields):
    return (1, _msg((2, name), *fields))


def test_modules_of_a_trace():
    stat = _msg((1, 7), (6, HLO))
    meta = _msg((1, 99), (2, "jit_run_phase(99)"), (5, stat))
    other = _msg((1, 98), (2, "jit_other(98)"))          # no HloProto
    xspace = _msg(
        _plane("/device:TPU:0", (4, _msg((1, 99), (2, _msg((2, "x")))))),
        _plane("/host:metadata",
               (5, _msg((1, 7), (2, _msg((1, 7), (2, "Hlo Proto"))))),
               (4, _msg((1, 99), (2, meta))),
               (4, _msg((1, 98), (2, other)))))
    got = [(n, bytes(p)) for n, p in spans.hlo_protos(xspace)]
    assert got == [("jit_run_phase(99)", HLO)]


def test_device_ops_take_their_module_scopes():
    ev = lambda name, start, dur: SimpleNamespace(
        name=name, start_ns=start, duration_ns=dur)
    plane = SimpleNamespace(lines=[
        SimpleNamespace(name="XLA Modules", events=[
            ev("jit_run_phase(99)", 100, 50), ev("jit_other(98)", 200, 9)]),
        SimpleNamespace(name="XLA Ops", events=[
            ev("%dus1 = bf16[2,8] dynamic-update-slice(a)", 110, 5),
            ev("%u1 = bf16[8] convert(p)", 120, 5),
            ev("%u1 = f32[8] add(p)", 201, 2),      # same name, other module
            ev("%u2 = f32[8] add(p)", 300, 2)])])   # no module around it
    modules = {"jit_run_phase(99)": spans.hlo_scopes(HLO)}
    rows = spans._device_ops(plane, modules)
    assert [r[1:] for r in rows] == [
        [110, 5, "", "engine.pack"], [120, 5, "engine.unpack", "engine.unpack"],
        [201, 2, "", ""], [300, 2, "", ""]]


FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "v5e_smollm_m2_scoped_boundary.json")


def test_recorded_v5e_boundary():
    """85 ms of a TPU v5e trace of smollm360m-m2-s128-k4 around the
    boundary between the phases of steps 17-20 and 21-24: the last
    step's update and averaging event, the gap, and the start of the
    next phase's unpack."""
    f = spans.load_json(FIXTURE)
    t0, t1 = f["stretch"]
    got = spans.scope_ns(f["ops"][0], t0, t1)
    assert got == {"engine.batch": 2287, "engine.unpack": 26518574,
                   "engine.fwd_bwd": 3586606, "engine.pack": 6625563,
                   "engine.update": 27136965, "engine.average": 18208229,
                   "": 6170, "named": 61978668, "busy": 82084394,
                   "inferred:engine.batch": 19,
                   "inferred:engine.unpack": 19927422,
                   "inferred:engine.fwd_bwd": 170043,
                   "inferred:engine.update": 2072}
    # every leaf counted once: the scopes and the rest add up to the
    # busy time (no two leaves overlap on the op line)
    assert sum(v for k, v in got.items() if k not in ("named", "busy")
               and not k.startswith("inferred:")) == got["busy"]
    # what is not named by its own op_name is what the rule placed
    assert got["named"] + sum(v for k, v in got.items() if k.startswith(
        "inferred:")) + got[""] == got["busy"]
    by_op = {}
    for a, b, ev in tr._clip(tr.leaves(f["ops"][0]), t0, t1):
        by_op.setdefault(ev[0].split(" = ")[0], ev[3:])
    # the update kernel, by its name; the plane's convert to bf16 that
    # XLA hoisted out of the unpack keeps no op_name, and counts under
    # the unpack that consumes it
    assert by_op["%opt_step.9"] == ["engine.update", "engine.update"]
    assert by_op["%convert.1801"] == ["", "engine.unpack"]
    assert by_op["%maximum_convert_fusion.2"] == ["engine.pack",
                                                  "engine.pack"]
    ctx = _ctx(f, t0, t1, 1)
    read = {n: spec.reader(n)(ctx) for n in READERS}
    assert read["fwd_bwd_ms"] == pytest.approx(3586606 / 1e6)
    assert read["avg_event_ms"] == pytest.approx(18208229 / 1e6)
    # fetch of step 17's phase ends at 1,105,942,248; the dispatch of
    # step 21's at 1,107,951,648
    assert read["host_bubble_ms"] == pytest.approx(2.0094)
    assert read["input_wait_ms"] == pytest.approx(0.08481)
