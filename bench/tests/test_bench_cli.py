"""The command refuses to measure without a TPU and outside a checkout,
and prints no result line either way."""

import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "qwen15-roam-chat", "--seed", str(2 ** 33 + 1), "--seconds", "1"]


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(script), *ARGS], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    p = _run(ROOT, ROOT / "bench" / "run.py")
    assert p.returncode == 1, p.stderr[-2000:]
    assert "No CPU fallback" in p.stderr
    assert '"correct"' not in p.stdout


def test_outside_a_checkout_no_result(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _run(tmp_path, tmp_path / "bench" / "run.py")
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
