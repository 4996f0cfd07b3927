"""The entry points' persistent compilation cache (launch/compile_cache):
placed by ``JAX_COMPILATION_CACHE_DIR`` when set, else at the fixed
``<repo>/.jax_cache``; and a second process finds what the first wrote."""
import os
import subprocess
import sys

import jax
import pytest

from repro.launch import compile_cache as CC

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    return calls


def test_env_var_wins_and_nothing_is_set(monkeypatch, updates, tmp_path):
    monkeypatch.setenv(CC.ENV_VAR, str(tmp_path))
    assert CC.use_compile_cache() == str(tmp_path)
    assert updates == []


def test_default_is_the_fixed_repo_dir(monkeypatch, updates):
    monkeypatch.delenv(CC.ENV_VAR, raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert CC.DEFAULT_DIR == want
    assert CC.use_compile_cache() == want
    assert ("jax_compilation_cache_dir", want) in updates
    # the path is the same in every process: no temp name, PID or time
    assert CC.use_compile_cache() == want


_PROBE = r"""
import collections, jax, jax.numpy as jnp
from repro.launch.compile_cache import use_compile_cache
n = collections.Counter()
jax.monitoring.register_event_listener(lambda e, **k: n.update([e]))
use_compile_cache()
jax.jit(lambda x: jnp.sin(x) @ x.T + 1.0)(jnp.ones((64, 64))).block_until_ready()
print("HITS", n["/jax/compilation_cache/cache_hits"])
"""


def test_second_process_hits_the_cache(tmp_path):
    cache = tmp_path / "cache"
    env = {**os.environ, CC.ENV_VAR: str(cache),
           "JAX_ENABLE_COMPILATION_CACHE": "true",   # off in the suite
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
           "PYTHONPATH": os.path.join(REPO, "src")}
    hits = []
    for _ in range(2):
        r = subprocess.run([sys.executable, "-c", _PROBE], env=env,
                           cwd=tmp_path, capture_output=True, text=True,
                           timeout=120)
        assert r.returncode == 0, r.stderr[-2000:]
        hits.append(int(r.stdout.split("HITS")[-1]))
    assert hits[0] == 0 and hits[1] >= 1, hits
    assert any(cache.iterdir())
    # nothing was written beside the cache directory
    assert sorted(p.name for p in tmp_path.iterdir()) == ["cache"]
