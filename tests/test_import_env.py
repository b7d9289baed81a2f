"""Importing the library leaves the XLA environment alone: no module under
``src/repro`` may set ``XLA_FLAGS`` as a side effect of being imported (a
forced host-device count would change what every later jax user sees).

Each case imports one directory's modules in a fresh interpreter, so a
module that another test already imported in this worker is still
imported here.  Only ``XLA_FLAGS`` is compared: importing
``jax.experimental.pallas`` sets ``CUDA_ROOT`` on its own."""

import json
import os
import pathlib
import subprocess
import sys

import jax  # noqa: F401  (jax's own import-time settings are the baseline)
import pytest

import repro

_ROOT = pathlib.Path(next(iter(repro.__path__)))
_DIRS = sorted(p.name for p in _ROOT.iterdir()
               if p.is_dir() and any(p.rglob("*.py")))

_PROG = """
import importlib, json, os, pathlib, sys
import jax
root, name = pathlib.Path(sys.argv[1]), sys.argv[2]
before = os.environ.get("XLA_FLAGS")
changed = []
for path in sorted((root / name).rglob("*.py")):
    parts = path.relative_to(root.parent).with_suffix("").parts
    if parts[-1] == "__init__":
        parts = parts[:-1]
    mod = ".".join(parts)
    importlib.import_module(mod)
    if os.environ.get("XLA_FLAGS") != before:
        changed.append(mod)
        if before is None:
            del os.environ["XLA_FLAGS"]
        else:
            os.environ["XLA_FLAGS"] = before
print(json.dumps(changed))
"""


@pytest.mark.parametrize("directory", _DIRS)
def test_import_leaves_xla_flags(directory):
    r = subprocess.run(
        [sys.executable, "-c", _PROG, str(_ROOT), directory],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "PYTHONPATH": str(_ROOT.parent)})
    assert r.returncode == 0, r.stderr[-2000:]
    assert json.loads(r.stdout.strip().splitlines()[-1]) == []
