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
