"""``repro.compile_cache.enable()``: where the persistent cache lives, and
that a second process finds what the first compiled."""
import os
import subprocess
import sys

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache as cc

from repro import compile_cache

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

PROBE = """
import jax, jax.numpy as jnp
from repro import compile_cache
hits = []
jax.monitoring.register_event_listener(
    lambda e, **kw: hits.append(e) if e.endswith("cache_hits") else None)
print(compile_cache.enable())
jax.jit(lambda x: jnp.sin(x) * 2.0 + 1.0)(jnp.ones(3)).block_until_ready()
print(len(hits))
"""


@pytest.fixture
def restore_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    was = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in was.items():
        jax.config.update(k, v)
    cc.reset_cache()


@pytest.mark.parametrize("env_dir", [None, "cache-from-env"])
def test_enable_places_the_cache(env_dir, monkeypatch, tmp_path,
                                 restore_cache_config):
    monkeypatch.delenv(compile_cache.MIN_TIME_VAR, raising=False)
    if env_dir is None:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        assert compile_cache.enable() == compile_cache.DEFAULT_DIR
        assert jax.config.jax_compilation_cache_dir == compile_cache.DEFAULT_DIR
        repo = os.path.dirname(os.path.abspath(SRC))
        assert compile_cache.DEFAULT_DIR == os.path.join(repo, ".jax_cache")
    else:
        path = str(tmp_path / env_dir)
        monkeypatch.setenv(compile_cache.ENV_VAR, path)
        before = jax.config.jax_compilation_cache_dir
        assert compile_cache.enable() == path
        # JAX reads the variable itself: no path is set in code
        assert jax.config.jax_compilation_cache_dir == before
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_a_second_process_hits_the_cache(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           compile_cache.ENV_VAR: str(tmp_path),
           "PYTHONPATH": os.path.abspath(SRC)}
    env.pop(compile_cache.MIN_TIME_VAR, None)
    hits = []
    for _ in range(2):
        out = subprocess.run([sys.executable, "-c", PROBE], env=env,
                             capture_output=True, text=True, timeout=120,
                             check=True).stdout.split()
        assert out[0] == str(tmp_path)
        hits.append(int(out[-1]))
    assert hits[0] == 0 and hits[1] > 0, hits
    assert os.listdir(tmp_path)
