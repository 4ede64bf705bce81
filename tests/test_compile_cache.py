"""The compile cache can be placed from outside (lightgbmv1_tpu/__init__.py).

``JAX_COMPILATION_CACHE_DIR`` set: the package sets nothing (it does not
even import jax; JAX reads the variable itself).  Unset: the cache lives
at ``<checkout>/.jax_cache`` — the same path from every process and every
working directory, because the directory is part of the cache key.  Each
probe is its own process: the suite itself runs with the cache off
(tests/conftest.py).
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROBE = ("import sys, lightgbmv1_tpu\n"
         "print('jax' in sys.modules)\n"
         "import jax\n"
         "print(jax.config.jax_compilation_cache_dir)\n")


def _probe(cwd, cache_env=None):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = REPO
    if cache_env is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_env
    out = subprocess.run([sys.executable, "-c", PROBE], env=env, cwd=cwd,
                         capture_output=True, text=True, check=True,
                         timeout=120).stdout.split()
    return out[0] == "True", out[1]


def test_env_var_wins_and_code_sets_nothing(tmp_path):
    outside = str(tmp_path / "placed_from_outside")
    imported_jax, cache_dir = _probe(str(tmp_path), cache_env=outside)
    assert not imported_jax, "helper touched jax although the env var is set"
    assert cache_dir == outside


def test_default_is_checkout_and_stable_across_processes(tmp_path):
    other = tmp_path / "elsewhere"
    other.mkdir()
    first = _probe(str(tmp_path))
    second = _probe(str(other))
    assert first == second == (True, os.path.join(REPO, ".jax_cache"))
