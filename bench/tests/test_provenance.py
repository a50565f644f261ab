"""The second witness for the scopes of instructions without op_name
(``bench/provenance.py``): its lineage on three made-up pass dumps, and
its verdict on the cell's phase compiled for a described v5e."""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

from bench import provenance as pv

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
B = "jit(run_phase)/while/body/closed_call/"
FWD, PACK = B + "vmap(engine.fwd_bwd)/dot", B + "vmap(engine.pack)/concatenate"
UNPACK = B + "vmap(engine.unpack)/reshape"


def _line(name, shape, op, operands="", op_name="", extra=""):
    meta = f', metadata={{op_name="{op_name}"}}' if op_name else ""
    return f"  %{name} = {shape} {op}({operands}){extra}{meta}\n"


# before: the pack concatenates two gradients; the unpack reshapes a
# slice of the plane
BEFORE = "".join([
    "ENTRY %main (p: bf16[8]) -> (bf16[8], bf16[2,2]) {\n",
    _line("p", "bf16[8]", "parameter", "0"),
    _line("f", "bf16[4]", "fusion", "%p", FWD, ", calls=%fused"),
    _line("g", "bf16[4]", "negate", "%f", FWD),
    _line("c", "bf16[8]", "concatenate", "%f, %g", PACK),
    _line("s", "bf16[4]", "slice", "%p", UNPACK),
    _line("r", "bf16[2,2]", "reshape", "%s", UNPACK),
    "  ROOT %t = (bf16[8], bf16[2,2]) tuple(%c, %r)\n", "}\n"])
# a pass turns the concatenation into a buffer and two updates with no
# op_name, and puts a copy loop (a computation of its own) before the
# unpack's reshape, which it keeps
AFTER = "".join([
    "%body (x: (bf16[4])) -> (bf16[4]) {\n",
    _line("x", "(bf16[4])", "parameter", "0"),
    _line("e", "bf16[4]", "get-tuple-element", "%x", "", ", index=0"),
    "  ROOT %y = (bf16[4]) tuple(%e)\n", "}\n",
    "ENTRY %main (p: bf16[8]) -> (bf16[8], bf16[2,2]) {\n",
    _line("p", "bf16[8]", "parameter", "0"),
    _line("f", "bf16[4]", "fusion", "%p", FWD, ", calls=%fused"),
    _line("g", "bf16[4]", "negate", "%f", FWD),
    _line("buf", "bf16[8]", "custom-call", "",
          extra=', custom_call_target="AllocateBuffer"'),
    _line("d1", "bf16[8]", "dynamic-update-slice", "%buf, %f"),
    _line("d2", "bf16[8]", "dynamic-update-slice", "%d1, %g"),
    _line("s", "bf16[4]", "slice", "%p", UNPACK),
    _line("lt", "(bf16[4])", "tuple", "%s"),
    _line("w", "(bf16[4])", "while", "%lt",
          extra=", condition=%cond, body=%body"),
    _line("wo", "bf16[4]", "get-tuple-element", "%w", "", ", index=0"),
    _line("r", "bf16[2,2]", "reshape", "%wo", UNPACK),
    "  ROOT %t = (bf16[8], bf16[2,2]) tuple(%d2, %r)\n", "}\n"])


def _renumbered(text):
    """The same module after a pass that renames every instruction."""
    import re
    return re.sub(r"%(\w+)", r"%\1.7", text)


def test_lineage_on_made_up_dumps(tmp_path):
    paths = []
    for i, text in enumerate((BEFORE, AFTER, _renumbered(AFTER))):
        path = tmp_path / f"module_0001.jit_run_phase.x.{i:04d}.pass.txt"
        path.write_text(text)
        paths.append(str(path))
    assert pv.dumps(str(tmp_path)) == paths
    ins, origin = pv.origins(paths)
    got = {n[:-2]: origin[n] for n in ins}
    # made from the concatenation: the pack's; the loop before the
    # reshape it rewired: the unpack's, body included
    assert {k: got[k] for k in ("buf", "d1", "d2", "w", "lt", "wo", "e",
                                "x", "y")} == {
        "buf": "engine.pack", "d1": "engine.pack", "d2": "engine.pack",
        "w": "engine.unpack", "lt": "engine.unpack", "wo": "engine.unpack",
        "e": "engine.unpack", "x": "engine.unpack", "y": "engine.unpack"}
    assert got["f"] == got["g"] == "engine.fwd_bwd"
    rows = pv.compare(ins, origin, {"d1.7": ("", "engine.pack"),
                                    "d2.7": ("", "engine.fwd_bwd")})
    by = {r[0]: r for r in rows}
    assert by["d1.7"][3:] == [16, "engine.pack", "engine.pack"]
    assert pv.summary([by["d1.7"], by["d2.7"]]) == {
        "bytes": {"engine.pack | engine.pack": 16,
                  "engine.fwd_bwd | engine.pack": 16}, "agree_share": 0.5}


@pytest.mark.skipif(importlib.util.find_spec("libtpu") is None,
                    reason="no TPU compiler here")
def test_rule_against_the_compiler_on_one_layer(tmp_path):
    """The cell's phase at one layer, compiled for a described v5e: the
    rule and the witness agree on every instruction without op_name of
    a megabyte or more, but for the embedding's gradient (2 workers x
    49,152 rows of 960): a scatter of the backward pass that XLA leaves
    unnamed and whose consumers are the pack's, so the rule counts it
    under engine.pack."""
    rows_path = tmp_path / "rows.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled")
    run = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "provenance.py"),
         "--workload", "smollm360m-m2-s128-k4", "--layers", "1",
         "--dump", str(tmp_path / "dump"), "--out", str(rows_path)],
        env=env, capture_output=True, text=True, timeout=600)
    assert run.returncode == 0, run.stderr[-3000:]
    out = json.loads(run.stdout.strip().splitlines()[-1])
    rows = json.loads(rows_path.read_text())
    assert out["passes"] > 100 and out["agree_share"] > 0.95
    differ = {(r[2], r[4], r[5]) for r in rows
              if r[3] >= 2 ** 20 and r[4] != r[5]}
    assert differ == {("bf16[98304,960]", "engine.pack", "engine.fwd_bwd")}
    kinds = {(r[1], r[2]): (r[4], r[5]) for r in rows if r[3] >= 2 ** 20}
    width = 47185920 + 9832320 + 960
    # the plane's convert to bf16, hoisted out of the unpack
    assert kinds[("convert", f"bf16[2,{width}]")] == ("engine.unpack",) * 2
    # the pack's concatenation, lowered to updates of one buffer
    assert {v for (op, _), v in kinds.items()
            if op == "dynamic-update-slice"} <= {("engine.unpack",) * 2,
                                                 ("engine.pack",) * 2}
