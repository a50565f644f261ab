"""From a profiler trace to the numbers the metric readers take.

:func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` writes and
keeps what the reduction needs, as plain lists: per device, the events
of its op line (the head of their HLO text, start, duration); on the
host, every event with its thread. :func:`save` and :func:`load_json`
keep that form on disk, which is how the tests hold a small recorded
trace. The rest are pure functions of that form, clipped to a window:
busy time (the union of op intervals), idle gaps and what the host was
doing in each, and the time of the ops a predicate picks.
"""
from __future__ import annotations

import glob
import json
import os
import re

_DEVICE = re.compile(r"^/device:TPU:(\d+)")
OP_LINE = "XLA Ops"
# "%name.12 = <result type> opcode(" at the head of an op's HLO text
_HEAD = re.compile(r"%([^ =]+) = (.*?) ([a-z][a-z0-9_\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short(text: str) -> str:
    """An op's HLO text cut to its head (name, result type, opcode) and
    its custom-call target: all the readers look at."""
    m = _HEAD.match(text)
    if not m:
        return text[:200]
    t = _TARGET.search(text)
    return m.group(0)[:400] + (f' custom_call_target="{t.group(1)}"'
                               if t else "")


def op(ev):
    """(kind, result type, opcode) of a device op: the kind is its HLO
    name without the number (``fusion``, ``convolution_add_fusion``,
    ``dynamic-update-slice``), ``tpu_custom_call`` for a Mosaic kernel."""
    m = _HEAD.match(ev[0])
    if not m:
        return ev[0][:40], "", ""
    kind = re.sub(r"\.\d+$", "", m.group(1))
    if 'custom_call_target="tpu_custom_call"' in ev[0]:
        kind = "tpu_custom_call"
    return kind, m.group(2), m.group(3)


def load(trace_dir: str) -> dict:
    """The newest ``.xplane.pb`` under ``trace_dir``, reduced to
    ``{"devices": {id: [event, ...]}, "host": [event, ...]}``; a device
    event is ``[op text (see short), start_ns, dur_ns]`` from the
    device's op line, a host event ``[name, start_ns, dur_ns, thread]``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        dev = _DEVICE.match(plane.name)
        if dev:
            out["devices"][int(dev.group(1))] = [
                [short(e.name), e.start_ns, e.duration_ns]
                for line in plane.lines if line.name == OP_LINE
                for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns > 0:
                        out["host"].append([e.name, e.start_ns,
                                            e.duration_ns, line.name])
    return out


def summary(trace_dir: str) -> dict:
    """Planes, lines, event counts and sample events with every stat:
    what to look at before writing a reader against a new trace."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    data = ProfileData.from_file(paths[-1])
    out = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            sample = [[e.name, e.start_ns, e.duration_ns,
                       {k: str(v)[:300] for k, v in e.stats}]
                      for e in evs[:: max(1, len(evs) // 12)][:12]]
            lines.append({"line": line.name, "events": len(evs),
                          "sample": sample})
        out.append({"plane": plane.name, "lines": lines})
    return {"file": paths[-1], "planes": out}


def save(trace: dict, path: str) -> None:
    with open(path, "w") as f:
        json.dump(trace, f)


def load_json(path: str) -> dict:
    with open(path) as f:
        t = json.load(f)
    t["devices"] = {int(k): v for k, v in t["devices"].items()}
    return t


# --------------------------------------------------------------------------
# reductions over [t0, t1]
# --------------------------------------------------------------------------

def span(trace: dict, name: str):
    """(start, end) of the first host event called ``name``, or None."""
    for ev in trace["host"]:
        if ev[0] == name:
            return ev[1], ev[1] + ev[2]
    return None


def _clip(evs, t0, t1):
    for ev in evs:
        a, b = max(ev[1], t0), min(ev[1] + ev[2], t1)
        if b > a:
            yield a, b, ev


def nest(evs) -> list:
    """The op line's events with their self time: [(event, self_ns,
    is_leaf)]. An op that contains others (a ``while``, a
    ``conditional``, a called computation) keeps only the time no
    contained op covers."""
    order = sorted(evs, key=lambda e: (e[1], -e[2]))
    selft = [e[2] for e in order]
    leaf = [True] * len(order)
    stack = []
    for i, e in enumerate(order):
        while stack and order[stack[-1]][1] + order[stack[-1]][2] <= e[1]:
            stack.pop()
        if stack:
            selft[stack[-1]] -= e[2]
            leaf[stack[-1]] = False
        stack.append(i)
    return list(zip(order, selft, leaf))


def leaves(evs) -> list:
    """The ops that contain no other op: what the device executes."""
    return [e for e, _, is_leaf in nest(evs) if is_leaf]


def busy_ns(evs, t0, t1) -> int:
    """Length of the union of the events' intervals inside [t0, t1]."""
    total, end = 0, t0
    for a, b, _ in sorted(_clip(evs, t0, t1), key=lambda x: x[0]):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def gaps(evs, t0, t1) -> list:
    """Intervals [(start, end)] inside [t0, t1] that no event covers."""
    out, end = [], t0
    for a, b, _ in sorted(_clip(evs, t0, t1), key=lambda x: x[0]):
        if a > end:
            out.append((end, a))
        end = max(end, b)
    if t1 > end:
        out.append((end, t1))
    return out


def op_ns(evs, pred, t0, t1) -> int:
    """Time inside [t0, t1] covered by the events for which
    ``pred(event)`` holds (overlaps counted once)."""
    return busy_ns([ev for ev in evs if pred(ev)], t0, t1)


def label(ev) -> str:
    """A device op's kind and result type, which groups the same op of
    every step (and of every layer of the same shape)."""
    kind, typ, _ = op(ev)
    return f"{kind} {re.sub(r'{[^}]*}', '', typ)[:80]}"


def top_ops(evs, t0, t1, n=10) -> list:
    """The ``n`` op labels with the most self time among the ops that
    start and end inside [t0, t1], as [label, seconds]."""
    acc = {}
    for ev, self_ns, _ in nest(evs):
        if ev[1] >= t0 and ev[1] + ev[2] <= t1:
            k = label(ev)
            acc[k] = acc.get(k, 0) + self_ns
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in top]


def idle_gaps(device_leaves, host, t0, t1, n=10, skip=("bench.window",)):
    """The ``n`` longest stretches in [t0, t1] in which the device ran
    no op, each named by the host event that overlaps it most
    (``thread: name``), as [label, seconds]."""
    host = [ev for ev in host if ev[0] not in skip]
    out = []
    for a, b in sorted(gaps(device_leaves, t0, t1),
                       key=lambda g: g[0] - g[1])[:n]:
        best, lab = 0, "no host event"
        for ev in host:
            ov = min(b, ev[1] + ev[2]) - max(a, ev[1])
            if ov > best:
                best, lab = ov, f"{ev[3]}: {ev[0]}"
        out.append([lab, (b - a) / 1e9])
    return out


if __name__ == "__main__":
    import sys
    print(json.dumps(summary(sys.argv[1]), indent=1))
