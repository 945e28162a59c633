"""The harness refuses to measure where it cannot: with no TPU, and in a
checkout that holds only the benchmark."""
import os
import shutil
import subprocess
import sys

from run import HERE, ROOT

ARGS = ["--workload", "osg-day-sweep-capacity", "--seed", "3000000000",
        "--seconds", "1", "--trace", "0"]


def harness(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(script)] + ARGS, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def no_result(proc):
    return not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_no_tpu_exits_nonzero_without_a_result():
    proc = harness(ROOT, HERE / "run.py")
    assert proc.returncode == 1
    assert "no TPU" in proc.stderr
    assert no_result(proc)


def test_benchmark_alone_exits_nonzero_without_a_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    proc = harness(tmp_path, tmp_path / "chipbench" / "run.py")
    assert proc.returncode != 0
    assert no_result(proc)
