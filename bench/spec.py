"""Finding a cell's pieces by name.

``BENCHMARK.json`` names the cells; each piece is a file of its own,
so a later cell, traffic mix or metric is added by adding files:

- configuration: the ``file`` its ``configs`` entry names;
- traffic mix: ``bench/traffic/<traffic>.json``;
- limits of the correctness check: ``bench/limits/<workload>.json``;
- metric reader: ``bench/metrics/<metric>.py``, a ``read(ctx)`` that
  returns a number, or None where it finds nothing to read;
- model kind: ``bench/models/<model>.py`` (the program's side) and
  ``bench/ref/<model>.py`` (the plain reference).
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _json(path):
    with open(path) as f:
        return json.load(f)


def benchmark(root=ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def cell(name: str, root=ROOT) -> dict:
    """Everything one cell needs: its ``workload`` entry, its
    configuration, traffic and limits, and its metric entries."""
    b = benchmark(root)
    wl = {w["name"]: w for w in b["workloads"]}.get(name)
    if wl is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    conf = {c["name"]: c for c in b["configs"]}[wl["config"]]
    config = _json(os.path.join(root, conf["file"]))
    config["name"] = conf["name"]
    return {
        "workload": wl,
        "config": config,
        "traffic": _json(os.path.join(root, "bench", "traffic",
                                      wl["traffic"] + ".json")),
        "limits": _json(os.path.join(root, "bench", "limits",
                                     name + ".json")),
        "end_to_end": [m for m in b["end_to_end"] if _in(m, name)],
        "per_layer": [m for m in b["per_layer"] if _in(m, name)],
    }


def _in(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def reader(metric: str, root=ROOT):
    """The ``read(ctx)`` of ``bench/metrics/<metric>.py``."""
    path = os.path.join(root, "bench", "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def model(kind: str):
    """(program adapter, reference) modules of a model kind."""
    return (importlib.import_module(f"bench.models.{kind}"),
            importlib.import_module(f"bench.ref.{kind}"))


def peaks(device_kind: str, root=ROOT) -> dict:
    """The peaks of ``device_kind``; a device not in the table is an
    error, never a default."""
    table = _json(os.path.join(root, "bench", "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "bench/peaks.json")
    return table["devices"][device_kind]
