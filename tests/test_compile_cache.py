"""The entry points' persistent compilation cache: the directory named by
JAX_COMPILATION_CACHE_DIR when it is set, else a fixed path inside the
checkout.  Each case runs in a fresh process, since JAX reads the
variable when it starts."""

import os
import subprocess
import sys

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src")

PROBE = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import CHECKOUT_CACHE_DIR, enable_compile_cache
path = enable_compile_cache()
print("PATH", path)
print("CONFIG", jax.config.jax_compilation_cache_dir)
print("CHECKOUT", CHECKOUT_CACHE_DIR)
if {compile}:
    jax.jit(lambda x: x * 3 + 1)(jnp.ones(5)).block_until_ready()
"""


def _probe(env_dir, compile_):
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    r = subprocess.run([sys.executable, "-c",
                        PROBE.format(compile=compile_)],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr
    return dict(line.split(" ", 1) for line in r.stdout.splitlines())


def test_cache_goes_where_the_environment_says(tmp_path):
    out = _probe(tmp_path / "cache", True)
    assert out["PATH"] == out["CONFIG"] == str(tmp_path / "cache")
    entries = os.listdir(tmp_path / "cache")
    assert any(e.startswith("jit_") for e in entries), entries


def test_cache_defaults_to_a_fixed_path_in_the_checkout():
    out = _probe(None, False)
    root = os.path.realpath(os.path.join(SRC, ".."))
    assert out["PATH"] == out["CONFIG"] == out["CHECKOUT"]
    assert out["PATH"] == os.path.join(root, ".jax_cache")
