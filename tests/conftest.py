import numpy as np
import pytest

from repro.core import CostParams
from repro.traces import SynthConfig, synth_trace


@pytest.fixture(scope="session")
def params():
    return CostParams()


@pytest.fixture(scope="session")
def small_trace():
    return synth_trace(SynthConfig(
        kind="netflix", n_items=60, n_servers=20, n_requests=4000,
        t_max=8.0, bundle_cover=1.0, bundle_zipf=0.7, seed=7))


#: test sizes of the benchmark's traffic mixes that the ``SMALL`` table of
#: ``tests/bench/test_bench_correct.py`` does not hold; that file runs
#: every cell of ``BENCHMARK.json`` at its table's size
BENCH_TEST_TRAFFIC = {
    "sweep-fig7-axes": {"trace_requests": 60, "traces": 1},
}


def pytest_collection_modifyitems(items):
    for mod in {item.module for item in items
                if getattr(item, "module", None) is not None
                and item.module.__name__ == "test_bench_correct"}:
        for name, size in BENCH_TEST_TRAFFIC.items():
            mod.SMALL.setdefault(name, size)
