"""Where the persistent compilation cache goes (repro.compile_cache).

Each case runs in a fresh interpreter: JAX reads JAX_COMPILATION_CACHE_DIR
once, at import, and the cache is process-global."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = """
import json, sys
import jax, jax.numpy as jnp
from repro.compile_cache import enable_compile_cache
returned = enable_compile_cache()
if sys.argv[1] == "compile":
    jax.jit(lambda x: jnp.sin(x) * 2.0)(jnp.arange(8.0)).block_until_ready()
print(json.dumps({"returned": returned,
                  "jax": jax.config.jax_compilation_cache_dir}))
"""


@pytest.mark.parametrize("env_set", [True, False])
def test_cache_dir(tmp_path, env_set):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src") + os.pathsep
               + env.get("PYTHONPATH", ""))
    cache = str(tmp_path / "cache")
    if env_set:
        env.update(JAX_COMPILATION_CACHE_DIR=cache,
                   JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
                   JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1")
    # the unset case compiles nothing, so it writes nothing into the checkout
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT, "compile" if env_set else "resolve"],
        env=env, capture_output=True, text=True, timeout=240, cwd=str(tmp_path))
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    want = cache if env_set else os.path.join(ROOT, ".jax_cache")
    assert out == {"returned": want, "jax": want}
    if env_set:
        assert os.listdir(cache), "no cache entry was written"
