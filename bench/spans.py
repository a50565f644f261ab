"""The program's own names in a profiler trace.

The phase program runs each part of its step under one named scope
(``engine.batch``, ``engine.unpack``, ``engine.fwd_bwd``,
``engine.pack``, ``engine.update``, ``engine.average``), which reaches
each compiled instruction's ``op_name``; ``PhaseEngine.run`` marks its
host loop with ``engine.*`` spans that carry the phase's first step
(``step``). :func:`load` keeps both from the newest ``.xplane.pb`` of a
cell's trace, as plain lists:

- ``ops``: per device, ``[op text (trace.short), start_ns, dur_ns, own
  scope, scope]`` for each event of its op line: the scope its
  instruction's ``op_name`` names, and the scope it is counted under
  (:func:`hlo_scopes`), ``""`` for none;
- ``spans``: ``[name, start_ns, dur_ns, step, thread]`` for each host
  event called ``engine.*`` (thread: the host line's name and index).

:func:`save` and :func:`load_json` keep that form on disk for the
tests. The reductions are pure functions of it over [t0, t1]: device
self time per scope per step (leaf ops, as ``bench.trace`` nests them)
and host time between spans.
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys

from bench import trace as tr

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCOPES = ("engine.batch", "engine.unpack", "engine.fwd_bwd", "engine.pack",
          "engine.update", "engine.average")
# a scope as one component of a name stack, bare or under transformations
# (``vmap(engine.fwd_bwd)``, ``transpose(jvp(engine.fwd_bwd))``)
_SCOPE = re.compile(r"(?:^|/)(?:[a-z_]+\()*(engine\.[a-z_]+)\)*(?=/|$)")
MODULE_LINE = "XLA Modules"
_NAME = re.compile(r"%([^ =]+) = ")


def load(trace_dir: str) -> dict:
    """The newest ``.xplane.pb`` under ``trace_dir`` in the form above."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    with open(paths[-1], "rb") as f:
        raw = f.read()
    modules = {name: hlo_scopes(proto) for name, proto in hlo_protos(raw)}
    out = {"ops": {}, "spans": []}
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        dev = tr._DEVICE.match(plane.name)
        if dev:
            out["ops"][int(dev.group(1))] = _device_ops(plane, modules)
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith("engine."):
                        step = dict(e.stats).get("step")
                        out["spans"].append([
                            e.name, e.start_ns, e.duration_ns,
                            None if step is None else int(step),
                            f"{line.name}#{i}"])
    return out


def _device_ops(plane, modules) -> list:
    """The op line's events, each with the scope of its instruction in
    the module that ran it (the ``XLA Modules`` event around it)."""
    runs = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                  for line in plane.lines if line.name == MODULE_LINE
                  for e in line.events)
    starts = [r[0] for r in runs]
    out = []
    for line in plane.lines:
        if line.name != tr.OP_LINE:
            continue
        for e in line.events:
            i = bisect.bisect_right(starts, e.start_ns) - 1
            mod = modules.get(runs[i][2], {}) if i >= 0 and \
                e.start_ns < runs[i][1] else {}
            head = _NAME.match(e.name)
            own, sc = mod.get(head.group(1) if head else "", ("", ""))
            out.append([tr.short(e.name), e.start_ns, e.duration_ns, own, sc])
    return out


def for_cell(ctx):
    """The program's names in the cell's trace (``bench/.trace/<cell>``),
    read once per run and kept on ``ctx.trace`` for every reader; None
    where the run left no trace."""
    t = ctx.trace
    if t is None:
        return None
    if not hasattr(t, "names"):
        t.names = None
        try:
            t.names = load(os.path.join(ROOT, "bench", ".trace",
                                        ctx.workload))
        except FileNotFoundError:
            pass
    return t.names


def save(spans: dict, path: str) -> None:
    with open(path, "w") as f:
        json.dump(spans, f)


def load_json(path: str) -> dict:
    with open(path) as f:
        t = json.load(f)
    t["ops"] = {int(k): v for k, v in t["ops"].items()}
    return t


# --------------------------------------------------------------------------
# the compiled modules: which scope each instruction ran under
# --------------------------------------------------------------------------
# A TPU op event holds its instruction's HLO text and times, not its
# name stack. The profiler keeps each module it saw run as a serialized
# HloProto (the ``Hlo Proto`` stat of the ``/host:metadata`` plane),
# whose instructions hold their ``op_name``. ``ProfileData`` does not
# expose that plane, so the two messages are read here from the
# protobuf wire format.

def _varint(b, i):
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(b):
    """(field number, wire type, value) of each field of a message."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 1:
            v, i = b[i:i + 8], i + 8
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield num, wire, v


def _ints(wire, v) -> list:
    """A repeated integer field's value: packed, or one varint."""
    if wire == 0:
        return [v]
    out, i = [], 0
    while i < len(v):
        x, i = _varint(v, i)
        out.append(x)
    return out


def hlo_protos(xspace: bytes):
    """(module name, serialized HloProto) of each module in a trace: the
    name is the one the device's ``XLA Modules`` line gives its runs."""
    b = memoryview(xspace)
    for num, _, plane in _fields(b):           # XSpace.planes
        if num != 1:
            continue
        f = list(_fields(plane))
        if not any(n == 2 and bytes(v) == b"/host:metadata" for n, _, v in f):
            continue
        stat_names = {}                         # XPlane.stat_metadata
        for _, _, entry in (x for x in f if x[0] == 5):
            for n, _, meta in _fields(entry):
                if n == 2:
                    d = {k: v for k, _, v in _fields(meta)}
                    stat_names[d.get(1, 0)] = bytes(d.get(2, b"")).decode()
        for _, _, entry in (x for x in f if x[0] == 4):  # event_metadata
            for n, _, meta in _fields(entry):
                if n != 2:
                    continue
                name, proto = "", None
                for k, _, v in _fields(meta):
                    if k == 2:
                        name = bytes(v).decode()
                    elif k == 5:               # XStat
                        st = {sk: sv for sk, _, sv in _fields(v)}
                        if stat_names.get(st.get(1)) == "Hlo Proto":
                            proto = st.get(6)
                if proto is not None:
                    yield name, proto


def hlo_scopes(proto) -> dict:
    """{instruction name: (own scope, scope)} of a serialized HloProto.

    The own scope is what the instruction's ``op_name`` names
    (:func:`scope`). XLA makes some instructions with no ``op_name``:
    a convert it hoists over a whole plane, a concatenation lowered to
    a chain of dynamic-update-slices, the loops it builds for large
    copies. Such an instruction takes the scope of the nearest named
    instructions that consume its result in its computation (the most
    of them; a tie goes to the earlier scope of the step), else of the
    nearest that produce its operands, else the scope of the
    instruction that calls its computation (a loop, a fusion, a
    branch).

    ``bench/provenance.py`` checks the rule against XLA's per-pass
    dumps of the same compile; PERF.md keeps what it found, with the
    instructions on which the two differ."""
    module = next(v for n, _, v in _fields(memoryview(proto)) if n == 1)
    ins, caller = {}, {}
    for n, _, comp in _fields(module):
        if n != 3:
            continue
        cid, members = None, []
        for k, w, v in _fields(comp):
            if k == 5:
                cid = v
            elif k == 2:
                d = {"operands": [], "called": [], "op_name": ""}
                for fk, fw, fv in _fields(v):
                    if fk == 1:
                        d["name"] = bytes(fv).decode()
                    elif fk == 35:
                        d["id"] = fv
                    elif fk == 36:
                        d["operands"] += _ints(fw, fv)
                    elif fk == 38:
                        d["called"] += _ints(fw, fv)
                    elif fk == 7:              # OpMetadata.op_name
                        for mk, _, mv in _fields(fv):
                            if mk == 2:
                                d["op_name"] = bytes(mv).decode()
                members.append(d)
        for d in members:                       # the id follows them
            d["own"] = scope(d["op_name"])
            d["comp"] = cid
            ins[d["id"]] = d
    users = {}
    for i, d in ins.items():
        for c in d["called"]:
            caller.setdefault(c, i)
        for o in d["operands"]:
            if o in ins and ins[o]["comp"] == d["comp"]:
                users.setdefault(o, []).append(i)
    near_users = _nearest(ins, lambda i: users.get(i, ()))
    near_operands = _nearest(ins, lambda i: [
        o for o in ins[i]["operands"]
        if o in ins and ins[o]["comp"] == ins[i]["comp"]])
    done = {}

    def resolve(i):
        chain = []
        while i not in done:
            d = ins[i]
            pick = d["own"] or _pick(near_users[i]) or _pick(near_operands[i])
            if pick or d["comp"] not in caller:
                done[i] = pick
                break
            chain.append(i)
            i = caller[d["comp"]]
        for j in chain:
            done[j] = done[i]
        return done[i]

    return {d["name"]: (d["own"], resolve(i)) for i, d in ins.items()}


def _nearest(ins, nbrs) -> dict:
    """For each instruction, the named instructions nearest to it along
    ``nbrs`` (through unnamed ones): {id: (distance, {scope: count})},
    distance None where none is reachable."""
    out = {}
    for root in ins:
        stack = [(root, False)]
        while stack:
            i, expanded = stack.pop()
            if i in out:
                continue
            todo = [j for j in nbrs(i) if j not in out and not ins[j]["own"]]
            if todo and not expanded:          # a computation is a DAG
                stack.append((i, True))
                stack.extend((j, False) for j in todo)
                continue
            best, count = None, {}
            for j in nbrs(i):
                if ins[j]["own"]:
                    dist, found = 1, {ins[j]["own"]: 1}
                elif out.get(j, (None,))[0] is not None:
                    dist, found = out[j][0] + 1, out[j][1]
                else:
                    continue
                if best is None or dist < best:
                    best, count = dist, {}
                if dist == best:
                    for k, v in found.items():
                        count[k] = count.get(k, 0) + v
            out[i] = (best, count)
    return out


def _pick(near) -> str:
    """The scope most of the nearest named instructions ran under."""
    counts = {k: v for k, v in near[1].items() if not k.startswith("mixed:")}
    if not counts:
        return ""
    return max(counts, key=lambda k: (counts[k], -SCOPES.index(k)))


# --------------------------------------------------------------------------
# device: self time per scope
# --------------------------------------------------------------------------

def scope(op_name: str) -> str:
    """The engine scope an op ran under; ``""`` for none. A fused op
    names its parts joined by ``;``; one whose parts ran under several
    scopes is ``mixed:<a>+<b>``."""
    found = sorted({m for part in op_name.split(";")
                    for m in _SCOPE.findall(part)})
    if len(found) > 1:
        return "mixed:" + "+".join(found)
    return found[0] if found else ""


def scope_ns(ops, t0, t1) -> dict:
    """Device time inside [t0, t1] of the leaf ops, by scope (``""``:
    none), with ``busy``, the union of the leaves' intervals, ``named``,
    the time of the leaves whose own ``op_name`` names their scope, and
    ``inferred:<scope>``, the time :func:`hlo_scopes` counts under a
    scope for leaves with no ``op_name``."""
    leaves = tr.leaves(ops)
    out = {"named": 0}
    for a, b, ev in tr._clip(leaves, t0, t1):
        out[ev[4]] = out.get(ev[4], 0) + (b - a)
        if ev[3] and ev[3] == ev[4]:
            out["named"] += b - a
        elif ev[4]:
            k = "inferred:" + ev[4]
            out[k] = out.get(k, 0) + (b - a)
    out["busy"] = tr.busy_ns(leaves, t0, t1)
    return out


def per_step_ms(ctx):
    """{scope: device ms per traced step}, averaged over the cell's
    devices, with ``unscoped``, ``mixed`` (ops fused across scopes),
    ``named``, ``inferred:<scope>`` and ``busy`` (:func:`scope_ns`);
    None without a trace, a scoped op or a traced step. The table goes
    to standard error once per run."""
    t, sp = ctx.trace, for_cell(ctx)
    if t is None or sp is None or not t.steps:
        return None
    devs = sorted(sp["ops"])[:ctx.chips]
    acc = {}
    for d in devs:
        for k, ns in scope_ns(sp["ops"][d], t.t0, t.t1).items():
            k = "unscoped" if k == "" else "mixed" if k.startswith(
                "mixed:") else "inferred:mixed" if k.startswith(
                "inferred:mixed:") else k
            acc[k] = acc.get(k, 0.0) + ns / 1e6 / t.steps / len(devs)
    if not any(k in acc for k in SCOPES):
        return None
    if not getattr(t, "scopes_logged", False):
        t.scopes_logged = True
        scoped = sum(acc.get(k, 0.0) for k in SCOPES)
        leaf = scoped + acc.get("unscoped", 0.0) + acc.get("mixed", 0.0)
        print(f"[bench] device ms per step by scope over {t.steps} steps: "
              f"{dict(sorted(acc.items()))!r}; leaf sum {leaf!r}; share "
              f"of busy in a scope {scoped / acc['busy']!r}, named by its "
              f"own op_name {acc['named'] / acc['busy']!r}", file=sys.stderr)
    return acc


def scoped_ms(ctx, metric: str, scopes) -> float | None:
    """The device ms per step under ``scopes`` (:func:`per_step_ms`).
    The part of it that :func:`hlo_scopes` placed, for ops with no
    ``op_name``, goes to standard error beside it: that part rests on
    the rule, which ``bench/provenance.py`` checks against the
    compiler's own history."""
    ms = per_step_ms(ctx)
    if ms is None:
        return None
    total = sum(ms.get(s, 0.0) for s in scopes)
    rule = sum(ms.get("inferred:" + s, 0.0) for s in scopes)
    print(f"[bench] {metric}: {total!r} ms per step, {rule!r} of it for "
          "ops with no op_name, placed by the rule", file=sys.stderr)
    return total


# --------------------------------------------------------------------------
# host: time between the loop's spans
# --------------------------------------------------------------------------

def named(spans, name, t0, t1) -> list:
    """The spans called ``name`` that end inside [t0, t1], by start."""
    return sorted((s for s in spans
                   if s[0] == name and t0 <= s[1] + s[2] <= t1),
                  key=lambda s: s[1])


def bubbles_ns(spans, t0, t1) -> list:
    """Per phase boundary inside [t0, t1]: from the end of phase n's
    ``engine.fetch`` to the end of phase n+1's ``engine.dispatch`` (the
    next step the driving thread dispatched), in ns."""
    disp = named(spans, "engine.dispatch", t0, t1)
    out = []
    for f in named(spans, "engine.fetch", t0, t1):
        nxt = [d for d in disp if d[3] > f[3]]
        if nxt:
            d = min(nxt, key=lambda d: d[3])
            out.append(d[1] + d[2] - (f[1] + f[2]))
    return out


def waits_ns(spans, t0, t1) -> list:
    """The durations of the ``engine.next_block`` spans that lie inside
    [t0, t1], in ns."""
    return [s[2] for s in named(spans, "engine.next_block", t0, t1)
            if s[1] >= t0]
