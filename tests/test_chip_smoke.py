"""``chip_smoke.py`` refuses to run, and prints no result, where JAX finds
no TPU: there is no CPU fallback for the chip check."""
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import chip_smoke  # noqa: E402


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_chip_smoke_refuses_a_host_without_tpu(argv, capsys):
    assert chip_smoke.main(argv) != 0
    captured = capsys.readouterr()
    assert '"ok"' not in captured.out
    assert "no TPU" in captured.err
