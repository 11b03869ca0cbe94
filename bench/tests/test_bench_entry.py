"""The command refuses to run without the chips a cell asks for, and in a
checkout without the program, printing no result either way."""
import json
import os
import shutil
import subprocess
import sys

import benchtest

ARGS = ["--workload", "paper-msr.daily", "--seed", "2147483659",
        "--seconds", "1", "--trace", "0"]


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(root, "bench", "run.py"), *ARGS],
        cwd=root, env=env, capture_output=True, text=True, timeout=120)


def _no_result(out: str) -> bool:
    for line in out.splitlines():
        try:
            if "metrics" in json.loads(line):
                return False
        except (ValueError, TypeError):
            continue
    return True


def test_refuses_a_cpu(tmp_path):
    # a copy of the benchmark beside the program, so that the run leaves
    # nothing in this checkout
    root = tmp_path / "co"
    shutil.copytree(benchtest.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(benchtest.ROOT, "BENCHMARK.json"), root)
    os.symlink(os.path.join(benchtest.ROOT, "src"), root / "src")
    p = _run(str(root))
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "TPU" in p.stderr


def test_refuses_without_the_program(tmp_path):
    root = tmp_path / "co"
    shutil.copytree(benchtest.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(benchtest.ROOT, "BENCHMARK.json"), root)
    p = _run(str(root))
    assert p.returncode != 0
    assert _no_result(p.stdout)
