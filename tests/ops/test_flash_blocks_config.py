"""Block-size resolution for the Pallas flash kernels.

An explicit PADDLE_TPU_FLASH_BLOCK_Q / _K overrides its own side; a
side with no variable set is the built-in 128. A bad value fails at
resolution, naming the variable, never inside kernel setup.
"""

import pytest

from paddle_tpu.ops.pallas import flash


@pytest.fixture(autouse=True)
def _no_block_env(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_FLASH_BLOCK_Q", raising=False)
    monkeypatch.delenv("PADDLE_TPU_FLASH_BLOCK_K", raising=False)


def test_builtin_default_without_file():
    assert flash.default_blocks() == (128, 128)


def test_env_overrides_tuned_file(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_FLASH_BLOCK_Q", "64")
    assert flash.default_blocks() == (64, 128)


def test_bad_env_value_still_raises(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_FLASH_BLOCK_Q", "abc")
    with pytest.raises(ValueError, match="PADDLE_TPU_FLASH_BLOCK_Q"):
        flash.default_blocks()
