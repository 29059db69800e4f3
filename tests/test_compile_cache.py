"""The persistent compile cache is placed from outside the program.

``jax.config.update`` is replaced by a recorder in every test, so the
cache is never turned on for the test session."""

import pathlib

import jax
import pytest

from repro.launch import compile_cache

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_env_placed_cache_is_left_to_jax(monkeypatch, updates, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert updates == []
    assert jax.config.jax_compilation_cache_dir == before


def test_unplaced_cache_has_one_fixed_path_in_checkout(monkeypatch, updates):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first = compile_cache.enable_compile_cache()
    second = compile_cache.enable_compile_cache()
    assert first == second == str(REPO / ".jax_cache")
    assert updates == [("jax_compilation_cache_dir", first)] * 2
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
