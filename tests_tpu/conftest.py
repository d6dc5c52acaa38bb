"""On-chip kernel tier: every shipped Pallas entry, compiled by Mosaic
and compared with its reference on a real TPU.

    python -m pytest tests_tpu -q        # on the chip, one process

Kept OUT of `tests/` because that tree's conftest pins the cpu
platform. The chip belongs to the one process that initializes the
backend, so this tier runs in-process and starts no children. On any
other backend the session FAILS at start — a tier that skips would
read as green without a single kernel having compiled.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def pytest_configure(config):
    config.addinivalue_line("markers", "tpu: requires a real TPU chip")
    import jax
    # The f32 oracle comparisons assume exact-f32 matmuls; without this
    # pin the TPU default runs einsums as bf16 MXU passes (~1e-3 error),
    # blowing the 2e-5/5e-4 tolerances. bf16 production precision is
    # exercised by the bf16 cases and by chip_smoke.py.
    jax.config.update("jax_default_matmul_precision", "highest")


def pytest_sessionstart(session):
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        pytest.exit(
            f"tests_tpu needs a TPU: jax.default_backend() is "
            f"{backend!r}. Run it on the chip (python -m pytest "
            f"tests_tpu); the CPU suite is tests/.", returncode=3)
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

