"""CPU rehearsal: every cell's traffic through the real two-node
ObjectStore path at a tiny size (JAX on the CPU), with correct true, and
run.py refusing the CPU."""

import os
import shutil
import subprocess
import sys
import time

import pytest

from lib import harness, spec
from plants import tiny

CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("name", CELLS)
def test_cell_rehearses_on_cpu(name, trace, no_compile_cache):
    cell = spec.load_cell(name)
    result = harness.run_cell(name, 2**33 + 7, 1.0, bool(trace),
                              t_process=time.perf_counter(),
                              require_tpu=False, overrides=tiny(cell))
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    if not trace:
        assert {m["name"] for m in cell.end_to_end} == set(result["metrics"])
    assert list(result)[-1] == "checks"


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    lines = proc.stdout.strip().splitlines()
    return not lines or not lines[-1].startswith("{")


def test_run_py_refuses_the_cpu():
    proc = _run_py(spec.ROOT)
    assert proc.returncode != 0 and _no_result(proc), proc.stderr[-2000:]


def test_run_py_refuses_a_bare_checkout(tmp_path):
    """A directory with only BENCHMARK.json and benchmark/ has no program."""
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH_DIR, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_py(tmp_path, {"PYTHONPATH": ""})
    assert proc.returncode != 0 and _no_result(proc), proc.stderr[-2000:]
