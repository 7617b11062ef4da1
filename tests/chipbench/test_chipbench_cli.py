"""The command line refuses to measure without a TPU, and a directory that
holds the benchmark's files alone cannot run at all."""

import os
import shutil
import subprocess
import sys

from _chipbench_tiny import ROOT

ARGS = ["--workload", "synth-initial", "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "chipbench/run.py", *ARGS], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=240)


def test_refuses_the_cpu():
    p = _run(ROOT)
    assert p.returncode == 2, p.stderr
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
