"""The control, at a size a test run holds: the reference with its
parameters in float8 (the precision below the configuration's bf16),
put in the program's place, must fail the cell's limits, while the
program passes them. ``bench/control.py`` makes the same readings on
the chip at the cell's own size."""
import jax

from bench import check, control, spec
from bench.tests.helpers import tiny_root


def test_control_fails_and_program_passes(tmp_path):
    root = tiny_root(tmp_path)
    lines = []
    s = control.readings("tiny-cell", [5, 2 ** 31 + 3], 2, jax.devices(),
                         root=root, out=lines.append)
    limits = spec.cell("tiny-cell", root)["limits"]
    assert all(s["program_max"][n] <= limits[n] for n in check.NAMES), s
    for what in ("control_min", "half_batch_min"):
        assert any(s[what][n] > limits[n] for n in check.NAMES), (what, s)
    # a reading beside the control, for the limits' record; it need not fail
    assert set(s["momentum_bf16_min"]) == set(check.NAMES)
