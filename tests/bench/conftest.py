"""Fixtures of the benchmark's tests: a tiny copy of the cells, and the
CPU devices."""

import pytest

from benchtiny import tiny_bench_dir


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return tiny_bench_dir(tmp_path_factory.mktemp("bench"))


@pytest.fixture(scope="module")
def cpu():
    import jax

    return jax.devices("cpu")
