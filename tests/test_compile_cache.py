"""`configs.backend.enable_compile_cache`: where the entry points keep JAX's
persistent compilation cache."""
from pathlib import Path

from repro.configs import backend

REPO = Path(__file__).resolve().parents[1]


def _record_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(backend.jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_env_var_wins_and_code_sets_nothing(monkeypatch, tmp_path):
    calls = _record_updates(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    backend.enable_compile_cache()
    assert calls == []


def test_default_is_fixed_repo_path(monkeypatch):
    calls = _record_updates(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    backend.enable_compile_cache()
    backend.enable_compile_cache()
    assert calls == [("jax_compilation_cache_dir", str(REPO / ".jax_cache"))] * 2
