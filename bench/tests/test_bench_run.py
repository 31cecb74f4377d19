"""The command refuses to run without what a cell needs: with no
accelerator, and in a directory that holds only BENCHMARK.json and the
benchmark's own files, it exits non-zero and prints no result."""
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _run(cwd):
    cmd = [sys.executable, "bench/run.py", "--workload", "internlm2-1.8b.steady",
           "--seed", str(2 ** 31 + 5), "--seconds", "1", "--trace", "0"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def _no_result(p):
    assert p.returncode != 0
    assert not any(line.lstrip().startswith("{")
                   for line in p.stdout.splitlines())


def test_no_accelerator_exits_without_a_result():
    p = _run(ROOT)
    _no_result(p)
    assert "accelerator" in p.stderr


def test_bare_benchmark_directory_exits_without_a_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    _no_result(_run(tmp_path))
