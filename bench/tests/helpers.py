"""A copy of the benchmark with one tiny cell, for runs on the CPU."""
import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TINY = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
            num_attention_heads=4, vocab_size=512)


def tiny_root(tmp, config="smollm-360m", traffic="m2-b4-s128-k4", chips=1,
              limits="smollm360m-m2-s128-k4", name="tiny-cell"):
    """``tmp`` holding ``BENCHMARK.json`` and ``bench/`` plus a cell
    ``name``: ``config`` at width 64 and 2 layers, ``traffic`` at batch 2
    x seq 16, the limits of cell ``limits``."""
    root = str(tmp)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns(".jax_cache", ".trace",
                                                  "__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    with open(os.path.join(ROOT, "bench", "configs", config + ".json")) as f:
        cfg = json.load(f)
    cfg.update(TINY, num_key_value_heads=2 if cfg["model_type"] == "llama"
               else 1)
    if cfg.get("sliding_window"):
        cfg["sliding_window"] = 8
    with open(os.path.join(ROOT, "bench", "traffic", traffic + ".json")) as f:
        tr = json.load(f)
    tr.update(batch=2, seq=16)
    _dump(cfg, root, "bench/configs/tiny.json")
    _dump(tr, root, "bench/traffic/tiny.json")
    shutil.copy(os.path.join(ROOT, "bench", "limits", limits + ".json"),
                os.path.join(root, "bench", "limits", name + ".json"))
    b["configs"].append({"name": "tiny", "source": "a test", "reduced": [],
                         "file": "bench/configs/tiny.json", "why": "tests"})
    b["workloads"].append({"name": name, "config": "tiny", "traffic": "tiny",
                           "chips": chips, "why": "tests"})
    _dump(b, root, "BENCHMARK.json")
    return root


def _dump(obj, root, rel):
    with open(os.path.join(root, rel), "w") as f:
        json.dump(obj, f)
