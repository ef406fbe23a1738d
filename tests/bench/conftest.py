"""Fixtures of the benchmark's own tests."""

import pytest

from bench_toy import make_toy_root


@pytest.fixture
def toy_root(tmp_path):
    """A toy copy of the benchmark whose cell runs on the CPU in seconds."""
    return make_toy_root(tmp_path / "checkout")
