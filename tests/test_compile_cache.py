"""``repro.compile_cache``: where JAX's persistent compilation cache goes.

``jax.config.update`` is replaced by a recorder, so these tests never
turn the cache on in the test process.
"""
import os

import jax

from repro import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KEEP_ALL = ("jax_persistent_cache_min_compile_time_secs", 0)


def _record_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    return calls


def test_dir_from_environment_is_left_to_jax(monkeypatch, tmp_path):
    calls = _record_updates(monkeypatch)
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert calls == [KEEP_ALL]


def test_default_dir_is_fixed_at_checkout_root(monkeypatch):
    calls = _record_updates(monkeypatch)
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    path = compile_cache.enable()
    assert path == os.path.join(REPO, ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", path), KEEP_ALL]
