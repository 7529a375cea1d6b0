"""Where the persistent compile cache goes (`utils.enable_compilation_cache`): the
environment places it, and otherwise one fixed directory inside the checkout does — the
directory is part of the cache key, so it must never move between runs."""

import os

import jax
import pytest

from dolomite_engine_tpu import utils
from dolomite_engine_tpu.utils import DEFAULT_COMPILATION_CACHE_DIR, enable_compilation_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def recorded(monkeypatch):
    """Force the cache on (it is TPU-only by default) but record what the function would
    configure and create instead of doing it — the test session itself stays uncached."""
    updates: list[tuple] = []
    made: list[str] = []
    monkeypatch.setenv("DOLOMITE_COMPILATION_CACHE", "1")
    monkeypatch.setattr(jax.config, "update", lambda key, value: updates.append((key, value)))
    monkeypatch.setattr(utils.os, "makedirs", lambda path, exist_ok=False: made.append(path))
    return updates, made


def test_environment_places_the_cache_and_code_sets_no_path(recorded, monkeypatch, tmp_path):
    updates, made = recorded
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "placed"))
    assert enable_compilation_cache() == str(tmp_path / "placed")
    assert made == [str(tmp_path / "placed")]
    # jax reads the variable itself: no jax.config.update of the directory by code
    assert [key for key, _ in updates] == ["jax_persistent_cache_min_compile_time_secs"]


def test_unset_the_cache_goes_to_one_fixed_ignored_path_in_the_checkout(recorded, monkeypatch):
    updates, made = recorded
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert enable_compilation_cache() == DEFAULT_COMPILATION_CACHE_DIR
    assert made == [DEFAULT_COMPILATION_CACHE_DIR]
    assert ("jax_compilation_cache_dir", DEFAULT_COMPILATION_CACHE_DIR) in updates
    # fixed: under the checkout's root, no home directory, temp name, pid or time in it
    assert DEFAULT_COMPILATION_CACHE_DIR == os.path.join(REPO, ".jax_compilation_cache")
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_compilation_cache/" in f.read().split()


@pytest.mark.parametrize("toggle", ["0", ""], ids=["switched_off", "cpu_default"])
def test_cache_stays_off(recorded, monkeypatch, toggle):
    updates, made = recorded
    monkeypatch.setenv("DOLOMITE_COMPILATION_CACHE", toggle)
    assert jax.default_backend() == "cpu"  # where "" means off: XLA:CPU AOT code is host-bound
    assert enable_compilation_cache() is None
    assert updates == [] and made == []
