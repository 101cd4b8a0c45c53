"""Where the persistent compile cache goes: the environment's choice when
``JAX_COMPILATION_CACHE_DIR`` is set, else one fixed, git-ignored path
inside the checkout. ``jax.config.update`` is intercepted, so no test turns
the cache on."""
import pathlib

import jax

from repro.launch.compile_cache import (ENV_VAR, compile_cache_dir,
                                        enable_compile_cache)

REPO = pathlib.Path(__file__).resolve().parents[1]


def _record_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    return calls


def test_env_var_wins_and_nothing_is_set(monkeypatch):
    assert compile_cache_dir({ENV_VAR: "/cache/elsewhere"}) == (
        "/cache/elsewhere", True)
    monkeypatch.setenv(ENV_VAR, "/cache/elsewhere")
    calls = _record_updates(monkeypatch)
    assert enable_compile_cache() == "/cache/elsewhere"
    assert calls == []  # JAX already read the variable itself


def test_fixed_ignored_path_inside_the_repo_otherwise(monkeypatch):
    path, from_env = compile_cache_dir({})
    assert not from_env
    assert path == str(REPO / ".jax_cache")
    assert compile_cache_dir({"TMPDIR": "/tmp/x", ENV_VAR: ""}) == (
        path, False)  # no tmp name, pid or time in it; empty var ignored
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()
    monkeypatch.delenv(ENV_VAR, raising=False)
    calls = _record_updates(monkeypatch)
    assert enable_compile_cache() == path
    assert calls == [("jax_compilation_cache_dir", path)]
