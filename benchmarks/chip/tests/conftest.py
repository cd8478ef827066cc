"""Fixtures of the chip benchmark's tests."""
import pytest

from chipbench_testlib import harness


@pytest.fixture
def cpu_run(monkeypatch):
    """Keep the process's compile-cache settings as the suite has them."""
    monkeypatch.setattr(harness, "use_compile_cache", lambda: "off")
