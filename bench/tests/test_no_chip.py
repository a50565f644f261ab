"""Without a TPU, or without the program beside it, a run exits
non-zero and prints no result."""
import os
import shutil
import subprocess
import sys

from bench.tests.helpers import ROOT

ARGS = ["--workload", "smollm360m-m2-s128-k4", "--seed", "3",
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True, timeout=300)


def _no_result(out):
    assert out.returncode != 0
    assert not any(line.lstrip().startswith("{")
                   for line in out.stdout.splitlines())


def test_cpu_host_gets_no_result():
    out = _run(ROOT)
    _no_result(out)
    assert "no TPU" in out.stderr


def test_benchmark_files_alone_get_no_result(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".trace"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    _no_result(_run(tmp_path))
