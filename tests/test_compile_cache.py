"""The persistent compilation cache helper: it honours
``JAX_COMPILATION_CACHE_DIR`` and otherwise uses one fixed directory inside
the checkout, the same across calls and processes.  `enable_compile_cache`
changes JAX's process-wide config, so it only runs in child processes
here."""

import os
import subprocess
import sys

from repro.compile_cache import ENV_VAR, compile_cache_dir

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT_CACHE = os.path.join(REPO, ".jax_cache")

PROBE = (
    "import jax\n"
    "from repro.compile_cache import enable_compile_cache\n"
    "got = enable_compile_cache()\n"
    "print(got)\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
)


def _child(env_dir):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop(ENV_VAR, None)
    if env_dir is not None:
        env[ENV_VAR] = env_dir
    proc = subprocess.run([sys.executable, "-c", PROBE], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout.split()


def test_default_dir_is_fixed_inside_checkout(monkeypatch):
    monkeypatch.delenv(ENV_VAR, raising=False)
    assert compile_cache_dir() == CHECKOUT_CACHE
    assert compile_cache_dir() == CHECKOUT_CACHE
    # a second process lands on the same directory, and JAX uses it
    assert _child(None) == [CHECKOUT_CACHE, CHECKOUT_CACHE]


def test_env_var_places_the_cache(monkeypatch, tmp_path):
    where = str(tmp_path / "cache")
    monkeypatch.setenv(ENV_VAR, where)
    assert compile_cache_dir() == where
    assert _child(where) == [where, where]
