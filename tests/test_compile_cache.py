"""The persistent compile cache: the environment's directory, else a fixed
in-checkout path. jax.config.update is captured, so the test process's own
cache stays off."""

from pathlib import Path

import jax

from repro.launch import compile_cache


def _capture(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_env_dir_is_left_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    calls = _capture(monkeypatch)
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert calls == []


def test_default_dir_is_fixed_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    calls = _capture(monkeypatch)
    path = compile_cache.enable_compile_cache()
    root = Path(__file__).resolve().parents[1]
    assert Path(path) == root / ".jax_cache"
    assert calls == [("jax_compilation_cache_dir", path)]
    # the same path on every call: no temp, pid or time in it
    assert compile_cache.enable_compile_cache() == path
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()
