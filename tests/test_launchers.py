"""CLI launcher integration tests (subprocess; reduced configs)."""

import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow  # excluded from the fast CI lane

_ENV = {**os.environ,
        "PYTHONPATH": os.path.join(os.path.dirname(__file__), "..", "src")}


def _run(args, timeout=560):
    r = subprocess.run([sys.executable, "-m"] + args, capture_output=True,
                       text=True, timeout=timeout, env=_ENV,
                       cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert r.returncode == 0, r.stderr[-2000:]
    return r.stdout


def test_train_cli():
    out = _run(["repro.launch.train", "--arch", "qwen2-0.5b", "--reduced",
                "--steps", "8", "--batch", "4", "--seq", "32"])
    assert "network train[qwen2-0.5b] verified" in out
    assert "loss" in out


def test_serve_cli():
    out = _run(["repro.launch.serve", "--arch", "qwen2-0.5b", "--reduced",
                "--requests", "4", "--slots", "2", "--max-new", "4"])
    assert "4 requests" in out
    assert "tok/s" in out
