"""Where compiled executables persist between processes.

One rule for every entry point that compiles (chip_smoke.py,
benchmark/run.py, the serving worker, the tools): when
``JAX_COMPILATION_CACHE_DIR`` is set the directory belongs to whoever
set it — JAX reads the variable itself and no code here names another.
Otherwise the cache lives at a fixed path inside the checkout,
``<root>/.jax_cache`` (git-ignored). The path is part of how a cache is
found again, so it is never a temp name, a pid or a timestamp.
"""

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def enable_compile_cache():
    """Turn the persistent compilation cache on for this process and
    return the directory in use. Call before the first compile."""
    import jax
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    # cache every compile, even fast ones (JAX's default floor is 1 s)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir
