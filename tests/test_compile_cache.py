"""The entry points' compilation-cache directory: the environment
variable wins, otherwise one fixed path inside the checkout."""
import os
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache as CC

REPO = Path(__file__).resolve().parents[1]


def test_environment_variable_wins():
    assert CC.cache_dir({CC.ENV_VAR: "/some/cache"}) == "/some/cache"


@pytest.mark.parametrize("environ", [{}, {CC.ENV_VAR: ""}])
def test_default_is_one_fixed_path_inside_the_checkout(environ):
    first = CC.cache_dir(environ)
    assert first == CC.cache_dir(environ) == str(REPO / ".jax_cache")
    assert Path(first).is_relative_to(REPO)


def test_default_path_is_git_ignored():
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


@pytest.mark.parametrize("env_value", [None, "/elsewhere/jax-cache"])
def test_enable_sets_jax_config_only_without_the_variable(monkeypatch,
                                                          env_value):
    before = jax.config.jax_compilation_cache_dir
    if env_value is None:
        monkeypatch.delenv(CC.ENV_VAR, raising=False)
    else:
        monkeypatch.setenv(CC.ENV_VAR, env_value)
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        path = CC.enable()
        if env_value is None:
            assert path == str(REPO / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
        else:
            # JAX reads the variable itself; enable() leaves it alone.
            assert path == env_value
            assert jax.config.jax_compilation_cache_dir is None
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert os.environ.get(CC.ENV_VAR) == env_value
