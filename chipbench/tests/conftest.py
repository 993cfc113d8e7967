"""Fixtures for the benchmark's own tests."""
import pytest

from benchlib import write_bench


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    """A run in a test leaves JAX's process-wide compile-cache settings as
    the rest of the suite expects them."""
    from chipbench import run

    monkeypatch.setattr(run, "_cache", lambda root: None)


@pytest.fixture
def small_bench(tmp_path):
    return write_bench(tmp_path)
