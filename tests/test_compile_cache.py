"""Where JAX's persistent compilation cache goes.

``backend.enable_compile_cache`` keeps the cache where
``JAX_COMPILATION_CACHE_DIR`` says, and otherwise at the fixed
``<checkout>/.jax_cache``.  Each case runs in a fresh interpreter so the
process-wide JAX config of the test worker is left alone.
"""
import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent

SNIPPET = """
import jax, jax.numpy as jnp
from repro.kernels import backend
print(backend.enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
if {compile}:
    jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).block_until_ready()
"""


def _run(env_extra, compile_=False):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update({"PYTHONPATH": str(ROOT / "src"), "JAX_PLATFORMS": "cpu"})
    env.update(env_extra)
    out = subprocess.run(
        [sys.executable, "-c", SNIPPET.format(compile=compile_)],
        check=True, text=True, capture_output=True, env=env, cwd=str(ROOT))
    return out.stdout.split()


def test_compile_cache_honours_env_dir(tmp_path):
    cache = tmp_path / "jax-cache"
    got = _run({"JAX_COMPILATION_CACHE_DIR": str(cache),
                "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"},
               compile_=True)
    assert got == [str(cache), str(cache)]
    assert any(cache.iterdir())            # the entry landed there


def test_compile_cache_default_is_fixed_checkout_path():
    want = str(ROOT / ".jax_cache")
    assert _run({}) == [want, want]
