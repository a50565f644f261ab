"""A later cell and a later per-layer metric are taken up from added
files alone: nothing that is already there is edited."""
import json
import os
from types import SimpleNamespace

from bench import spec
from bench.tests.helpers import tiny_root


def test_added_cell_and_metric_are_found(tmp_path):
    root = tiny_root(tmp_path)            # adds config, traffic, limits
    with open(os.path.join(root, "bench", "metrics", "steps_seen.py"), "w") as f:
        f.write("def read(ctx):\n    return ctx.window_steps or None\n")
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        b = json.load(f)
    b["per_layer"].append({"name": "steps_seen", "unit": "steps",
                           "better": "higher", "source": "program_counter",
                           "layer": "host loop", "moves": "tokens_per_s",
                           "workloads": ["tiny-cell"]})
    with open(path, "w") as f:
        json.dump(b, f)

    c = spec.cell("tiny-cell", root)
    assert c["config"]["hidden_size"] == 64 and c["traffic"]["seq"] == 16
    assert c["limits"]["loss_gap"] > 0
    names = [m["name"] for m in c["per_layer"]]
    assert "steps_seen" in names
    # a metric limited to other cells is not this cell's
    assert "collective_ms" not in names
    read = spec.reader("steps_seen", root)
    assert read(SimpleNamespace(window_steps=12)) == 12
    assert read(SimpleNamespace(window_steps=0)) is None
    # the cells already there are untouched
    assert spec.cell("smollm360m-m2-s128-k4", root)["traffic"]["seq"] == 128


def test_every_metric_has_a_reader():
    b = spec.benchmark()
    for m in b["end_to_end"] + b["per_layer"]:
        assert callable(spec.reader(m["name"])), m["name"]
    for w in b["workloads"]:
        c = spec.cell(w["name"])
        assert c["end_to_end"] and c["per_layer"], w["name"]
