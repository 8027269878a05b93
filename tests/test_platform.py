"""Where the program runs: interpret mode from the platform, and the
persistent compile cache the entry points switch on."""

import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp

from repro.platform import REPO_ROOT, pallas_interpret

ROOT = pathlib.Path(__file__).resolve().parents[1]

_PROBE = """
import jax, jax.numpy as jnp
from repro.platform import enable_compile_cache
path = enable_compile_cache()
print(path)
print(jax.config.jax_compilation_cache_dir)
if {compile}:
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
"""


def _probe(env_dir, compile_):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu")
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c",
                          _PROBE.format(compile=compile_)],
                         env=env, capture_output=True, text=True, timeout=120,
                         check=True)
    return out.stdout.split()


def test_interpret_follows_the_platform():
    assert jax.devices()[0].platform != "tpu"
    assert pallas_interpret(jnp.ones(3)) is True
    assert pallas_interpret() is True          # no array: default backend
    assert jax.jit(lambda x: x + pallas_interpret(x))(1.0) == 2.0


def test_compile_cache_goes_to_the_env_dir_when_set(tmp_path):
    path, configured = _probe(tmp_path, True)
    assert path == configured == str(tmp_path)
    assert any(tmp_path.iterdir())             # the compile was written there


def test_compile_cache_defaults_to_the_repo():
    path, configured = _probe(None, False)
    assert path == configured == str(REPO_ROOT / ".jax_cache")
    assert REPO_ROOT == ROOT
