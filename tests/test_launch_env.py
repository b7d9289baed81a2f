"""Launcher environment hygiene (``repro.launch._common``): the tcmalloc
preload is opt-in (``--tcmalloc``), announced on stderr, and never
clobbers an LD_PRELOAD the user already set.  The persistent compile cache
(``repro.launch.cache``) goes where ``JAX_COMPILATION_CACHE_DIR`` says, or
else to one fixed, git-ignored directory of the checkout."""

import argparse
import os
from pathlib import Path

import jax

from repro.launch import _common, cache


def _args(**kw):
    ns = argparse.Namespace(virtual_devices=0, tcmalloc=False)
    for k, v in kw.items():
        setattr(ns, k, v)
    return ns


def test_cluster_flags_include_tcmalloc_off_by_default():
    ap = argparse.ArgumentParser()
    _common.add_cluster_flags(ap)
    assert ap.parse_args([]).tcmalloc is False
    assert ap.parse_args(["--tcmalloc"]).tcmalloc is True


def test_tcmalloc_preload_is_opt_in_and_announced(monkeypatch, tmp_path,
                                                  capsys):
    lib = tmp_path / "libtcmalloc_minimal.so.4"
    lib.write_bytes(b"")
    monkeypatch.setattr(_common, "_TCMALLOC_CANDIDATES", (str(lib),))
    monkeypatch.delenv("LD_PRELOAD", raising=False)
    _common.apply_runtime_env(_args())  # default: allocator untouched
    assert "LD_PRELOAD" not in os.environ
    _common.apply_runtime_env(_args(tcmalloc=True))  # opt-in: set + notice
    assert os.environ["LD_PRELOAD"] == str(lib)
    assert "--tcmalloc" in capsys.readouterr().err


def test_tcmalloc_never_clobbers_existing_preload(monkeypatch, tmp_path):
    lib = tmp_path / "libtcmalloc_minimal.so.4"
    lib.write_bytes(b"")
    monkeypatch.setattr(_common, "_TCMALLOC_CANDIDATES", (str(lib),))
    monkeypatch.setenv("LD_PRELOAD", "/opt/mine.so")
    _common.apply_runtime_env(_args(tcmalloc=True))
    assert os.environ["LD_PRELOAD"] == "/opt/mine.so"


def test_compile_cache_defaults_to_a_fixed_ignored_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = Path(__file__).resolve().parents[1]
    before = jax.config.jax_compilation_cache_dir
    try:
        assert cache.enable_compile_cache() == str(root / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(root / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()


def test_compile_cache_env_var_wins(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # set nothing else
