"""utils/jax_cache.configure: where JAX's persistent compilation cache goes.

``jax.config.update`` is intercepted — the suite itself must never turn the
cache on (tests/conftest.py)."""

import os

import jax
import pytest

from theanompi_tpu.utils import jax_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def _platforms(monkeypatch, value):
    # what JAX_PLATFORMS / a programmatic pin leave in the config
    monkeypatch.setattr(type(jax.config), "jax_platforms",
                        property(lambda self: value), raising=False)


NAMES_IN_KEY = (jax_cache._NAMES_IN_KEY, True)


def test_sets_no_directory_when_the_env_var_places_it(monkeypatch, updates):
    monkeypatch.setenv(jax_cache.ENV_VAR, "/some/where")
    _platforms(monkeypatch, None)
    assert jax_cache.configure() is None
    assert updates == [NAMES_IN_KEY]


def test_the_names_in_key_option_is_one_this_jax_has():
    """The option's name is a string here: a jax that renamed it would make
    ``configure`` raise on the chip, where no test runs."""
    assert jax.config.jax_compilation_cache_include_metadata_in_key is False
    assert jax_cache._NAMES_IN_KEY == \
        "jax_compilation_cache_include_metadata_in_key"


def test_default_is_the_checkout_and_no_other_directory(monkeypatch, updates):
    monkeypatch.delenv(jax_cache.ENV_VAR, raising=False)
    _platforms(monkeypatch, None)
    want = os.path.join(REPO, ".jax_cache")
    assert jax_cache.configure() == want
    assert updates == [NAMES_IN_KEY, (jax_cache._OPTION, want)]
    # fixed: no tempfile, pid or clock in it
    assert jax_cache.configure() == want


def test_a_cpu_pinned_process_is_left_alone(monkeypatch, updates):
    monkeypatch.delenv(jax_cache.ENV_VAR, raising=False)
    _platforms(monkeypatch, "cpu")
    assert jax_cache.configure() is None
    assert updates == []


def test_the_default_dir_is_ignored_by_git():
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
