"""CacheSession against the numpy reference, policy by policy: every
registered policy under every JAX-expressible cost model, fed in ragged
chunks that split T_CG windows, prices like the numpy ``run_policy`` of
the same trace (counters exact, costs at 1e-9) and leaves the same
partition and window count."""
import pytest

from parity import (
    COST_MODELS,
    POLICIES,
    assert_same_costs,
    case,
    policy,
    reference,
    slices,
)
from repro.core import CacheSession


@pytest.mark.parametrize("model", COST_MODELS)
@pytest.mark.parametrize("name", POLICIES)
def test_session_matches_numpy(name, model):
    tr, _ = case(model)
    ref, ref_partition = reference(name, model)
    sess = CacheSession(policy(name, model), tr.n, tr.m)
    for a, b in slices(tr.n_requests, seed=5):
        sess.feed(tr.items[a:b], tr.servers[a:b], tr.times[a:b])
    assert_same_costs(ref.costs, sess.costs)
    assert sess.partition.canonical() == ref_partition
    assert sess.policy.n_windows == ref.n_windows
