"""Where the persistent compile cache is placed.  ``jax.config.update``
is replaced by a recorder, so no test turns the cache on."""
from __future__ import annotations

from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

CHECKOUT = Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("env", [True, False])
def test_use_compile_cache_follows_env_else_checkout(monkeypatch, tmp_path,
                                                     env):
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.append((name, value)))
    if env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.use_compile_cache()
    if env:
        # JAX reads the variable itself; the helper sets nothing
        assert path == tmp_path and updates == []
    else:
        assert path == CHECKOUT / ".jax_cache"
        assert updates == [("jax_compilation_cache_dir", str(path))]


def test_default_cache_dir_is_gitignored():
    ignored = (CHECKOUT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
