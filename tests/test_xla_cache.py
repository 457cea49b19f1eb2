"""xla_cache: where the persistent compile cache lives is decided from
outside. With ``JAX_COMPILATION_CACHE_DIR`` set the code sets no directory
(jax reads the variable itself); unset, it uses one fixed path inside the
checkout. The cache must refuse to arm in multi-host processes (divergent
collective decompositions across ranks — see
tests/parallel/mp_serve_worker.py) and honor the opt-out env."""

from __future__ import annotations

import os

import jax
import pytest

from agentcontrolplane_tpu import xla_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def recorded_updates(monkeypatch):
    """Record jax.config.update calls instead of mutating REAL global jax
    config (later compiles in this process must not be redirected)."""
    monkeypatch.setattr(xla_cache, "_enabled", False)
    updates: dict = {}
    monkeypatch.setattr(
        jax.config, "update", lambda k, v: updates.__setitem__(k, v)
    )
    return updates


def test_cache_disabled_for_multihost(monkeypatch):
    monkeypatch.setattr(xla_cache, "_enabled", False)
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    assert xla_cache.enable_persistent_compilation_cache() is False


def test_cache_env_opt_out(monkeypatch):
    monkeypatch.setattr(xla_cache, "_enabled", False)
    monkeypatch.setenv("ACP_XLA_CACHE", "0")
    assert xla_cache.enable_persistent_compilation_cache() is False


def test_env_placed_cache_sets_no_directory_in_code(
    monkeypatch, tmp_path, recorded_updates
):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "outside"))
    assert xla_cache.enable_persistent_compilation_cache() is True
    assert "jax_compilation_cache_dir" not in recorded_updates
    # the two "cache everything" settings still apply
    assert recorded_updates["jax_persistent_cache_min_entry_size_bytes"] == -1
    assert recorded_updates["jax_persistent_cache_min_compile_time_secs"] == 0.0
    assert not (tmp_path / "outside").exists()  # jax's to create, not ours


def test_unset_env_uses_the_fixed_in_checkout_path(monkeypatch, recorded_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    # the retired knob must not move the cache any more
    monkeypatch.setenv("ACP_XLA_CACHE_DIR", "/nonexistent/elsewhere")
    assert xla_cache.enable_persistent_compilation_cache() is True
    assert recorded_updates["jax_compilation_cache_dir"] == os.path.join(
        REPO, ".jax_cache"
    )
    # fixed: a second resolution gives the same path (never pid/time/tmp)
    xla_cache._enabled = False
    first = recorded_updates.pop("jax_compilation_cache_dir")
    assert xla_cache.enable_persistent_compilation_cache() is True
    assert recorded_updates["jax_compilation_cache_dir"] == first
