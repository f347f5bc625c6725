"""Where the persistent compile cache goes: placed from outside by
JAX_COMPILATION_CACHE_DIR (the program then sets nothing), otherwise
one fixed directory in the checkout, and never on a CPU backend."""

import os

import jax
import pytest

from thrill_tpu.api import Context
from thrill_tpu.common.config import COMPILE_CACHE_DIR
from thrill_tpu.parallel.mesh import MeshExec


@pytest.fixture
def cache_updates(monkeypatch):
    """jax.config.update calls that touch the compile cache."""
    calls = []
    real = jax.config.update

    def update(name, value):
        if "compilation_cache" in name:
            calls.append((name, value))
        else:
            real(name, value)

    monkeypatch.setattr(jax.config, "update", update)
    return calls


def _make_context():
    Context(MeshExec(devices=jax.devices("cpu")[:1])).close()


@pytest.mark.parametrize("backend,env,expect", [
    ("tpu", "/some/dir", []),
    ("tpu", None, [("jax_compilation_cache_dir", COMPILE_CACHE_DIR)]),
    ("cpu", None, []),
])
def test_compile_cache_placement(monkeypatch, cache_updates, backend, env,
                                 expect):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    _make_context()
    assert cache_updates == expect


def test_fixed_cache_dir_is_inside_the_checkout():
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    assert COMPILE_CACHE_DIR == os.path.join(root, ".jax_cache")
