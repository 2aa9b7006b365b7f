"""Placement of JAX's persistent compilation cache (launch/compile_cache.py).

Each case runs in a child process: turning the cache on is process-wide
JAX state, which must not leak into the other tests of a worker.
"""

import json
import os
import subprocess
import sys

import pytest

from repro.launch.compile_cache import DEFAULT_DIR, ENV_VAR

ROOT = os.path.join(os.path.dirname(__file__), "..")

_CHILD = r"""
import json, sys
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache

path = enable_compile_cache()
if sys.argv[1] == "compile":
    # cache every compile, however quick, so one tiny program lands on disk
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda a: jnp.sin(a) * 3.0 + 1.0)(jnp.arange(7.0)).block_until_ready()
print("RESULT:" + json.dumps({"path": path, "config": jax.config.jax_compilation_cache_dir}))
"""


def _run(mode: str, env_dir=None) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop(ENV_VAR, None)
    if env_dir is not None:
        env[ENV_VAR] = str(env_dir)
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD, mode], capture_output=True, text=True, env=env, timeout=300
    )
    if proc.returncode != 0:  # the child's whole stderr, not a tail
        pytest.fail(f"child exited {proc.returncode}:\n{proc.stderr}", pytrace=False)
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")][0]
    return json.loads(line[len("RESULT:"):])


def _listing(d) -> set:
    return {os.path.join(r, f) for r, _, fs in os.walk(d) for f in fs} if os.path.isdir(d) else set()


def test_env_var_places_the_cache_and_nothing_else(tmp_path):
    outside = tmp_path / "cache"
    before = _listing(DEFAULT_DIR)
    res = _run("compile", env_dir=outside)
    assert res["path"] == res["config"] == str(outside)
    assert _listing(outside), "the compile was not written to JAX_COMPILATION_CACHE_DIR"
    assert _listing(DEFAULT_DIR) == before, "the in-checkout cache was written too"


def test_default_is_fixed_ignored_path_in_checkout():
    res = _run("config")
    assert res["path"] == res["config"] == str(DEFAULT_DIR)
    assert str(DEFAULT_DIR.parent) == os.path.realpath(ROOT)
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert f"{DEFAULT_DIR.name}/" in f.read().split()
