"""The benchmark rehearsed on the CPU backend: the device check is
steered to the CPU and each cell cut to a size the CPU runs in seconds
(``common.SHRINK``), the way a test steers chip_smoke.py."""
import pytest

from bench_cpu import shrink


@pytest.fixture
def cpu_bench(monkeypatch):
    from bench import common
    monkeypatch.setattr(common, "PLATFORM", "cpu")
    monkeypatch.setattr(common, "SHRINK", shrink)
    return common
