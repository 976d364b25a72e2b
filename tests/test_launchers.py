"""Launcher CLIs and example entry points run end-to-end (subprocesses)."""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_cmd(args, timeout=900, devices=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    if not env.get("JAX_COMPILATION_CACHE_DIR"):
        # the examples keep a compile cache in the checkout; a test run
        # must not (same rule as tests/conftest.py)
        env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    if devices:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    out = subprocess.run([sys.executable] + args, capture_output=True,
                         text=True, timeout=timeout, env=env, cwd=ROOT)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_train_cli():
    out = run_cmd(["-m", "repro.launch.train", "--arch", "mamba2_13b",
                   "--steps", "2", "--seq-len", "32", "--global-batch", "2"])
    assert "loss" in out


def test_serve_cli():
    out = run_cmd(["-m", "repro.launch.serve", "--arch", "granite_moe_1b",
                   "--requests", "4"])
    assert "drained 4 requests" in out


def test_dryrun_cli_single_cell():
    out = run_cmd(["-m", "repro.launch.dryrun", "--arch", "gemma3_1b",
                   "--shape", "decode_32k", "--mesh", "single",
                   "--out", "/tmp/dryrun_test"], timeout=1200)
    assert "done; 0 failures" in out


@pytest.mark.slow
def test_elastic_restart_example():
    out = run_cmd(["examples/elastic_restart.py"], devices=8, timeout=1500)
    assert "elastic restart OK" in out
