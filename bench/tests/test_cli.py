"""The command refuses to measure anywhere but on a TPU checkout."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys

from conftest import ROOT

ARGS = ["--workload", "products-sage.rudder", "--seed", "3", "--seconds", "1", "--trace", "0"]


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, script, *ARGS], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


def test_without_a_tpu_exits_nonzero_and_prints_no_result():
    p = _run(ROOT, "bench/run.py")
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr


def test_outside_a_checkout_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    p = _run(tmp_path, "bench/run.py")
    assert p.returncode != 0 and p.stdout == ""
