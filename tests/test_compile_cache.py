"""Where ``enable_compile_cache`` puts JAX's persistent compilation
cache: in ``JAX_COMPILATION_CACHE_DIR`` when it is set (the helper sets
nothing), otherwise at the checkout's fixed ``.jax_cache/``.  Each case
runs in a fresh interpreter, since JAX opens its cache once per process.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

pytest.importorskip("jax")

ROOT = Path(__file__).resolve().parents[1]

PROBE = """
import json, jax, jax.numpy as jnp
from repro.compile_cache import DEFAULT_DIR, enable_compile_cache
where = enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
{compile}
print(json.dumps({{"where": where, "config": jax.config.jax_compilation_cache_dir,
                  "default": str(DEFAULT_DIR)}}))
"""


def _probe(env_dir, compile_code=""):
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c",
                          PROBE.format(compile=compile_code)],
                         capture_output=True, text=True, env=env, timeout=120)
    assert out.returncode == 0, out.stderr
    return json.loads(out.stdout.splitlines()[-1])


def test_env_dir_wins_and_receives_the_cache(tmp_path):
    cache = tmp_path / "jaxcache"
    got = _probe(cache, "jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(7))"
                        ".block_until_ready()")
    assert got["where"] == got["config"] == str(cache)
    assert got["where"] != got["default"]
    assert any(cache.iterdir()), "the compile wrote no cache entry"


def test_default_is_the_fixed_checkout_dir():
    got = _probe(None)
    assert got["where"] == got["config"] == got["default"]
    assert Path(got["default"]) == ROOT / ".jax_cache"
