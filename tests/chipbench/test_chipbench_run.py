"""run.py refuses to measure anywhere but on a TPU, and prints no result
when it cannot run."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


ARGS = ("--workload", "mamba2_780m.full_w4_s2048", "--seed", "2147483659",
        "--seconds", "1", "--trace", "0")


def test_no_tpu_no_result():
    proc = _run(ROOT, *ARGS)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path, *ARGS)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no program" in proc.stderr


def test_unknown_cell_no_result():
    proc = _run(ROOT, "--workload", "no_such_cell", "--seed", "1",
                "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
