import os
import subprocess
import sys

from benchutil import ROOT, copy_benchmark

ARGS = ["bench/run.py", "--workload", "isolated_c8", "--seed", "3000000001", "--seconds", "1",
        "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, *ARGS], cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def test_run_refuses_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    p = _run(copy_benchmark(tmp_path))
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
