"""A second witness for the scope of each instruction XLA made without
an ``op_name``.

``bench/spans.hlo_scopes`` gives an instruction that has no ``op_name``
the scope of the nearest named instructions that consume its result,
else of those that produce its operands, else of its caller. This tool
checks that rule against the compiler's own history: it compiles the
cell's phase program with an HLO dump after every pass, follows each
instruction of the optimized module back through the passes to the
instructions it was made from, and sets the scope it inherits that way
beside the rule's.

    python3 bench/provenance.py --workload <cell> [--layers N] [--out FILE]

On a host without a TPU it compiles for a described v5e (libtpu is
enough, as in ``bench/tests/test_aot_fit.py``); on a TPU host, for its
first chip. ``--layers`` cuts the model's depth; the widths stay. The
last line of standard output is a JSON summary: result bytes of the
instructions without ``op_name`` that run as device ops, by the rule's
scope and the witness's. ``--out`` keeps one row per such instruction.

How an instruction is followed. Between two dumps, one that keeps its
name, opcode and dimensions is the same instruction. A pass that
renumbers most of the module (a cloned loop body) is crossed by
structure: opcode, dimensions and ``op_name``, with the operands'
opcodes and dimensions where they tell candidates apart. What is left
was made by the pass. It is grouped with the other new instructions it
touches (as operand, user, or computation it calls), and the group
inherits the scope most of its neighbours had before the pass: the
instructions the pass removed and those whose operands it rewired.
"""
from __future__ import annotations

import argparse
import collections
import glob
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    sys.path[0] = ROOT
    sys.path.insert(1, os.path.join(ROOT, "src"))

from bench import spans  # noqa: E402

_LINE = re.compile(r"^\s+(?:ROOT )?%?([^ =]+) = (.+?) ([a-z][a-z0-9_\-]*)\((.*)$")
_LAYOUT = re.compile(r"\{[^}]*\}")
_OPERAND = re.compile(r"%([\w.\-]+)")
_CALLED = re.compile(r"\b(?:calls|body|condition|to_apply)=%?([\w.\-]+)")
_CALLED_LIST = re.compile(r"\b(?:branch_computations|called_computations)"
                          r"=\{([^}]*)\}")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_ARRAY = re.compile(r"^(\w+)\[([\d,]*)\]")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
          "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
          "u64": 8}
# instructions that are not device ops of their own
FREE = ("parameter", "get-tuple-element", "tuple", "constant", "bitcast")


def read_module(path: str) -> dict:
    """{name: instruction} of an HLO text dump. An instruction is a dict:
    ``opcode``, ``dims`` (its shape without layouts), ``operands``,
    ``called`` (computations), ``op_name`` and ``comp`` (the computation
    it is in)."""
    ins, comp = {}, None
    with open(path) as f:
        for line in f:
            if not line.startswith(" "):
                if line.rstrip().endswith("{"):
                    words = line.split()
                    comp = (words[1] if words[0] == "ENTRY"
                            else words[0]).lstrip("%")
                continue
            m = _LINE.match(line)
            if not m:
                continue
            name, shape, opcode, rest = m.groups()
            called = _CALLED.findall(rest)
            for group in _CALLED_LIST.findall(rest):
                called += [c.strip().lstrip("%") for c in group.split(",")]
            op_name = _OP_NAME.search(rest)
            ins[name] = {
                "opcode": opcode, "dims": _LAYOUT.sub("", shape),
                "operands": _OPERAND.findall(rest[:_close(rest)]),
                "called": called, "comp": comp,
                "op_name": op_name.group(1) if op_name else ""}
    return ins


def _close(s: str) -> int:
    """Index of the parenthesis that closes the operand list."""
    depth = 1
    for i, c in enumerate(s):
        depth += (c == "(") - (c == ")")
        if depth == 0:
            return i
    return len(s)


def _users(ins: dict) -> dict:
    out = collections.defaultdict(list)
    for n, d in ins.items():
        for o in d["operands"]:
            out[o].append(n)
    return out


def _key(ins, n):
    d = ins[n]
    return (d["opcode"], d["dims"], d["op_name"], tuple(
        (ins[o]["opcode"], ins[o]["dims"]) if o in ins else o
        for o in d["operands"]))


def _loose_key(ins, n):
    d = ins[n]
    return d["opcode"], d["dims"], d["op_name"]


def step(a: dict, b: dict, origin: dict) -> dict:
    """The origin scope of each instruction of dump ``b``, from those of
    the dump ``a`` before the pass."""
    new, left = {}, []
    for n, d in b.items():
        old = a.get(n)
        if old and old["opcode"] == d["opcode"] and old["dims"] == d["dims"]:
            new[n] = origin[n]
        else:
            left.append(n)
    if not left:
        return new
    lost = [n for n in a if n not in new]
    keys = [_key] + ([_loose_key] if len(lost) > len(a) / 2 else [])
    index = [collections.defaultdict(list) for _ in keys]
    for n in lost:
        for key, ix in zip(keys, index):
            ix[key(a, n)].append(n)
    made, twins = set(), set()
    for n in left:
        for key, ix in zip(keys, index):
            cand = ix.get(key(b, n))
            if cand:
                new[n] = collections.Counter(
                    origin[c] for c in cand).most_common(1)[0][0]
                twins.update(cand)
                break
        else:
            made.add(n)
    if made:
        removed = [n for n in lost if n not in twins]
        _inherit(a, b, origin, new, made, removed)
    return new


def _inherit(a, b, origin, new, made, removed):
    """Give each group of instructions made by a pass the scope most of
    its neighbours had before it."""
    users_a, users_b = _users(a), _users(b)
    members = collections.defaultdict(list)
    for n, d in b.items():
        members[d["comp"]].append(n)
    parent = {n: n for n in made}

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for n in made:
        near = list(b[n]["operands"])
        for c in b[n]["called"]:
            near += members.get(c, [])
        for m in near:
            if m in made:
                parent[find(n)] = find(m)
    groups = collections.defaultdict(list)
    for n in made:
        groups[find(n)].append(n)
    by_neighbour = collections.defaultdict(list)
    for r in removed:
        for x in a[r]["operands"] + users_a.get(r, []):
            by_neighbour[x].append(r)
    for group in groups.values():
        votes = collections.Counter()
        for n in group:
            for x in b[n]["operands"] + users_b.get(n, []):
                if x in made:
                    continue
                for r in by_neighbour.get(x, ()):
                    votes[origin[r]] += 1
                if x in a and a[x]["operands"] != b[x]["operands"]:
                    votes[new[x]] += 1
            for c in b[n]["called"]:
                for m in members.get(c, ()):
                    if m not in made:
                        votes[new[m]] += 1
        votes.pop("", None)
        source = votes.most_common(1)[0][0] if votes else ""
        for n in group:
            new[n] = spans.scope(b[n]["op_name"]) or source


def origins(paths: list) -> tuple:
    """(last dump's instructions, {name: origin scope}) over the dumps
    of one module in pass order."""
    a = read_module(paths[0])
    origin = {n: spans.scope(d["op_name"]) for n, d in a.items()}
    for path in paths[1:]:
        b = read_module(path)
        origin = step(a, b, origin)
        a = b
    return a, origin


def nbytes(dims: str) -> int:
    m = _ARRAY.match(dims)
    if not m or m.group(1) not in _BYTES:
        return 0
    n = _BYTES[m.group(1)]
    for x in m.group(2).split(","):
        n *= int(x) if x else 1
    return n


def compare(ins: dict, origin: dict, rule: dict) -> list:
    """One row per instruction without ``op_name`` that runs as a device
    op: [name, opcode, dims, result bytes, rule's scope, witness's]."""
    fused = {c for d in ins.values() if d["opcode"] == "fusion"
             for c in d["called"]}
    return [[n, d["opcode"], d["dims"], nbytes(d["dims"]),
             rule.get(n, ("", ""))[1], origin.get(n, "")]
            for n, d in ins.items()
            if not d["op_name"] and d["comp"] not in fused
            and d["opcode"] not in FREE]


def summary(rows: list) -> dict:
    """Result bytes by (rule's scope, witness's scope), and the share on
    which they agree."""
    by = collections.Counter()
    for _, _, _, nb, mine, theirs in rows:
        by[f"{mine or '-'} | {theirs or '-'}"] += nb
    total = sum(by.values())
    agree = sum(v for k, v in by.items() if k.split(" | ")[0] ==
                k.split(" | ")[1])
    return {"bytes": dict(by.most_common()), "agree_share":
            agree / total if total else None}


def dumps(directory: str) -> list:
    """The per-pass dumps of the phase module, in pass order."""
    paths = glob.glob(os.path.join(
        directory, "module_*.jit_run_phase.*.[0-9][0-9][0-9][0-9].*.txt"))
    order = re.compile(r"module_(\d+)\..*\.(\d{4})\.[^/]*$")
    return sorted(paths, key=lambda p: tuple(
        int(x) for x in order.search(p).groups()))


def compile_phase(workload: str, layers, directory: str):
    """Compile the cell's phase with an HLO dump after every pass; the
    serialized HloProto of the optimized module."""
    flags = (f" --xla_dump_to={directory} --xla_dump_hlo_pass_re=.*"
             " --xla_dump_hlo_module_re=.*run_phase.*")
    os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + flags
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from types import SimpleNamespace
    from bench import spec
    from bench.tests.test_aot_fit import phase_program
    jax.config.update("jax_enable_compilation_cache", False)
    if jax.default_backend() == "tpu":
        topo = SimpleNamespace(devices=jax.devices())
    else:
        from jax.experimental import topologies
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        # the kernels pick interpret mode from the default backend
        jax.default_backend = lambda: "tpu"
    cell = spec.cell

    def cut(name, root=spec.ROOT):
        c = cell(name, root)
        if layers:
            c["config"]["num_hidden_layers"] = layers
        return c

    spec.cell = cut
    try:
        compiled = phase_program(workload, topo).compile()
    finally:
        spec.cell = cell
    module = compiled.runtime_executable().hlo_modules()[0]
    proto = module.as_serialized_hlo_module_proto()
    return _hlo_proto(proto)


def _hlo_proto(module: bytes) -> bytes:
    """An HloModuleProto wrapped as the HloProto the profiler keeps."""
    n, size = len(module), bytearray()
    while True:
        size.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            break
    return b"\x0a" + bytes(size) + module


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--layers", type=int, default=0,
                    help="model depth to compile (default: the config's)")
    ap.add_argument("--dump", help="dump directory (default: "
                    "bench/.provenance/<cell>-l<layers>)")
    ap.add_argument("--out", help="JSON file for every row")
    a = ap.parse_args(argv)
    directory = a.dump or os.path.join(
        ROOT, "bench", ".provenance", f"{a.workload}-l{a.layers or 'all'}")
    if os.path.isdir(directory) and os.listdir(directory):
        sys.exit(f"{directory} is not empty: a dump of one compile only")
    os.makedirs(directory, exist_ok=True)
    proto = compile_phase(a.workload, a.layers, directory)
    paths = dumps(directory)
    final = glob.glob(os.path.join(
        directory, "module_*.jit_run_phase.*.after_optimizations.txt"))
    ins, origin = origins(paths + sorted(final)[-1:])
    rows = compare(ins, origin, spans.hlo_scopes(proto))
    if a.out:
        with open(a.out, "w") as f:
            json.dump(rows, f)
    out = {"workload": a.workload, "layers": a.layers or None,
           "passes": len(paths), **summary(rows)}
    print(json.dumps(out))
    return out


if __name__ == "__main__":
    main()
