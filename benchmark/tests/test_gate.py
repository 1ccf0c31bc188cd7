"""No mode runs anywhere but on a TPU, and none runs without the program."""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    CELLS = [w["name"] for w in json.load(f)["workloads"]]


def _run(cwd, cell, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t = time.monotonic()
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed", "0",
         "--seconds", "1", "--trace", str(trace)],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120,
    )
    return p, time.monotonic() - t


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_every_mode_exits_non_zero_on_the_cpu_before_compiling(cell, trace):
    p, took = _run(ROOT, cell, trace)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert p.stdout.strip() == ""  # no result line, no info line
    assert took < 60


def test_no_result_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p, _ = _run(str(tmp_path), CELLS[0], 0)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "distributed_groth16_tpu" in p.stderr


def test_the_load_generator_imports_neither_jax_nor_the_program():
    code = ("import sys; sys.argv = ['loadgen']; "
            "import benchmark.loadgen; "
            "bad = [m for m in sys.modules if m == 'jax' or "
            "m.startswith(('jax.', 'distributed_groth16_tpu'))]; "
            "sys.exit(1 if bad else 0)")
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, timeout=60)
    assert p.returncode == 0
