"""`python -m perf.run` refuses to run without a TPU, and in a checkout
that holds only the benchmark; `perf.control` refuses without a TPU."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _run(cwd, module="perf.run", args=("--workload", "grid10k.whatif")):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", module, *args, "--seconds", "1", "--trace", "0"]
        if module == "perf.run"
        else [sys.executable, "-m", module, *args, "--seconds", "1"],
        cwd=cwd,
        env=env,
        capture_output=True,
        text=True,
        timeout=120,
    )


def _no_result(proc):
    lines = proc.stdout.strip().splitlines()
    if lines:
        with pytest.raises(ValueError):
            json.loads(lines[-1])


def test_run_without_tpu_exits_nonzero_and_prints_no_result():
    proc = _run(ROOT, args=("--workload", "grid10k.whatif", "--seed", "2147483999"))
    assert proc.returncode != 0
    assert "TPU" in proc.stderr
    _no_result(proc)


def test_run_in_a_checkout_of_the_benchmark_alone_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        paths = json.load(f)["paths"]
    for p in paths:
        shutil.copytree(
            os.path.join(ROOT, p),
            os.path.join(tmp_path, p),
            ignore=shutil.ignore_patterns("__pycache__"),
        )
    proc = _run(str(tmp_path), args=("--workload", "fabric10k.converge", "--seed", "3"))
    assert proc.returncode != 0
    _no_result(proc)


def test_unknown_cell_exits_nonzero():
    proc = _run(ROOT, args=("--workload", "nope.none", "--seed", "1"))
    assert proc.returncode != 0
    _no_result(proc)


def test_control_without_tpu_exits_nonzero():
    proc = _run(
        ROOT, module="perf.control", args=("--workload", "grid10k.whatif", "--seeds", "1,2,3")
    )
    assert proc.returncode != 0
