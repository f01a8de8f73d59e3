"""bench/run.py refuses to measure anywhere but on the chip."""

import os
import shutil
import subprocess
import sys

from bench import harness


def _run(root, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, str(root / "bench" / "run.py"), *args],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


ARGS = ("--workload", "paper.mnist8.dpsvrg", "--seed", "3000000017",
        "--seconds", "1", "--trace", "0")


def test_without_a_tpu_exits_nonzero_and_prints_no_result():
    proc = _run(harness.ROOT, *ARGS)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path, *ARGS)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
