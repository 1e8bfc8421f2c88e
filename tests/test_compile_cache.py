"""The persistent compilation cache is placed from outside: JAX's own
``JAX_COMPILATION_CACHE_DIR`` wins, else a fixed ``<checkout>/.jax_cache``
— and nothing turns it on at import time. Checked in child processes so
this process's JAX config is untouched."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

PROBE = """
import jax
import repro.compile_cache as cc
print(jax.config.jax_compilation_cache_dir)
print(cc.use_compile_cache())
print(jax.config.jax_compilation_cache_dir)
print(jax.config.jax_persistent_cache_min_compile_time_secs)
"""


def _probe(env_dir):
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    env["PYTHONPATH"] = str(ROOT / "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", PROBE], capture_output=True, text=True,
        env=env, check=True,
    )
    return out.stdout.split()


def test_default_dir_is_fixed_under_the_checkout():
    before, returned, after, min_secs = _probe(None)
    assert before == "None"  # importing sets nothing
    assert returned == after == str(ROOT / ".jax_cache")
    assert float(min_secs) == 0.0


@pytest.mark.parametrize("name", ["cache-a", "cache-b"])
def test_env_var_dir_is_used_as_given(tmp_path, name):
    given = str(tmp_path / name)
    before, returned, after, _ = _probe(given)
    assert before == returned == after == given
