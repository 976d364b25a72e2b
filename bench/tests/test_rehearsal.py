"""The command without a chip, and in a checkout without the program: it
exits non-zero and prints no result line and no device metric.

    python -m pytest -q bench/tests/test_rehearsal.py
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]


def _run(cwd: Path, cell: str):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell, "--seed",
         "4294967311", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=600)


def _no_result(proc):
    assert proc.returncode != 0
    for line in proc.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


@pytest.mark.parametrize("cell", ["paper24.bp_fig3", "borg10k.bp_uniform"])
def test_no_tpu_no_result(cell):
    proc = _run(ROOT, cell)
    _no_result(proc)
    assert "needs a TPU" in proc.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "out"))
    proc = _run(tmp_path, "paper24.bp_fig3")
    _no_result(proc)
    assert "program is not in this checkout" in proc.stderr
