"""A run with no TPU, or without the program, exits non-zero and prints no
result line."""
import os
import shutil
import subprocess
import sys

from benchlib import ROOT

ARGS = ["--workload", "aibench16-fd-day", "--seed", str(2 ** 31 + 3),
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "chipbench/run.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=120)


def _no_result(proc):
    return not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_refuses_a_host_without_a_tpu():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert _no_result(proc)
    assert "no TPU" in proc.stderr


def test_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(str(tmp_path))
    assert proc.returncode != 0
    assert _no_result(proc)
    assert "No module named 'repro'" in proc.stderr
