import os
import subprocess
import sys

from conftest import ROOT


def test_run_reports_nothing_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", "bcd.resnet18_c100.deep", "--seed", "3000000019",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 3, p.stderr
    assert "no TPU" in p.stderr
    assert "correct" not in p.stdout


def test_run_fails_in_a_checkout_of_the_benchmark_alone(tmp_path):
    import shutil
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "bcd.resnet18_c100.deep", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "correct" not in p.stdout
