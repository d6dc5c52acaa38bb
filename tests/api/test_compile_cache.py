"""utils/compile_cache.py: JAX_COMPILATION_CACHE_DIR set -> the
directory is left to jax (no code names another); unset -> one fixed
path inside the checkout, the same from every process."""

import os
import subprocess
import sys

import jax

from paddle_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _updates(monkeypatch):
    calls = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.__setitem__(k, v))
    compile_cache.enable_compile_cache()
    return calls


def test_env_var_set_leaves_the_directory_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert "jax_compilation_cache_dir" not in _updates(monkeypatch)


def test_env_var_unset_uses_the_fixed_in_checkout_path(monkeypatch,
                                                       tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    calls = _updates(monkeypatch)
    assert calls["jax_compilation_cache_dir"] == \
        os.path.join(REPO, ".jax_cache")
    # the same path from another process in another directory: nothing
    # in it depends on cwd, pid or time
    env = dict(os.environ, PYTHONPATH=REPO)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, "-c",
         "from paddle_tpu.utils import compile_cache as c; "
         "print(c.DEFAULT_DIR)"],
        env=env, cwd=str(tmp_path), capture_output=True, text=True,
        timeout=120)
    assert out.stdout.strip() == calls["jax_compilation_cache_dir"], \
        out.stderr
