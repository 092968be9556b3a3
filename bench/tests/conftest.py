import pytest

from bench.tests import cells


@pytest.fixture
def sharded_bench(tmp_path, monkeypatch):
    """``BENCHMARK.json`` with the four-chip cell ``cells.SHARDED`` added."""
    return cells.add_sharded(tmp_path, monkeypatch)
