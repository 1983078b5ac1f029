"""``bench/run.py`` refuses to measure without a TPU and prints no result."""
import json
import os
import shutil
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def _run(cwd, **env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ckks14_serve_steady",
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **env))


def _no_result(p):
    for line in p.stdout.splitlines():
        try:
            assert "correct" not in json.loads(line)
        except json.JSONDecodeError:
            pass


def test_cpu_backend_is_refused():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "not a TPU" in p.stderr
    _no_result(p)


def test_fails_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    _no_result(p)
