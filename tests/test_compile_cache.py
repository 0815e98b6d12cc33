"""Where the persistent compilation cache lives: $JAX_COMPILATION_CACHE_DIR
when set (and no other directory in code), else the fixed
<repo>/.jax_cache.  Each case runs in a fresh CPU process, since the
cache is process-global JAX state."""

import json
import os
import subprocess
import sys

from blance_tpu.utils.compile_cache import REPO_CACHE_DIR

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = r"""
import json, sys
sys.path.insert(0, {repo!r})
import jax, jax.numpy as jnp
from blance_tpu.utils.compile_cache import enable_compile_cache
used = enable_compile_cache()
if {compile!r}:
    jax.jit(lambda x: x * 2 + 1)(jnp.arange(7)).block_until_ready()
print(json.dumps([used, jax.config.jax_compilation_cache_dir]))
"""


def _child(env_dir, compile_):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run(
        [sys.executable, "-c", _CHILD.format(repo=REPO, compile=compile_)],
        env=env, capture_output=True, text=True, check=True, timeout=120)
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_env_dir_is_used_and_written(tmp_path):
    cache = str(tmp_path / "cache")
    assert _child(cache, True) == [cache, cache]
    assert os.listdir(cache)


def test_repo_dir_without_env():
    assert REPO_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    assert _child(None, False) == [REPO_CACHE_DIR, REPO_CACHE_DIR]
