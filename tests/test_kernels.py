"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.clique_density import clique_pair_edges
from repro.kernels.crm_update import crm_update
from repro.kernels.packed_lookup import packed_lookup


@pytest.mark.parametrize("B,n", [(7, 5), (64, 60), (200, 130), (300, 257)])
@pytest.mark.parametrize("dtype", [np.float32, np.int8])
def test_crm_update_sweep(B, n, dtype):
    rng = np.random.default_rng(B * n)
    H = (rng.random((B, n)) < 0.1).astype(dtype)
    got = crm_update(jnp.asarray(H), interpret=True)
    want = ref.crm_ref(jnp.asarray(H).astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=0)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 40), st.integers(2, 70), st.integers(0, 2**31 - 1))
def test_crm_update_property(B, n, seed):
    rng = np.random.default_rng(seed)
    H = (rng.random((B, n)) < 0.2).astype(np.float32)
    got = np.asarray(crm_update(jnp.asarray(H), interpret=True))
    want = np.asarray(ref.crm_ref(jnp.asarray(H)))
    assert np.array_equal(got, want)
    assert np.array_equal(got, got.T) and np.diag(got).sum() == 0


@pytest.mark.parametrize("k,n", [(5, 8), (37, 70), (130, 200)])
def test_clique_density_sweep(k, n):
    rng = np.random.default_rng(k + n)
    M = (rng.random((k, n)) < 0.15).astype(np.float32)
    A = (rng.random((n, n)) < 0.25).astype(np.float32)
    got = clique_pair_edges(jnp.asarray(M), jnp.asarray(A), interpret=True)
    want = ref.clique_pair_edges_ref(jnp.asarray(M), jnp.asarray(A))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("dtype", [np.float32, np.int32])
@pytest.mark.parametrize("R,C,omega,d", [(4, 6, 5, 16), (17, 9, 3, 32)])
def test_packed_lookup_sweep(R, C, omega, d, dtype):
    rng = np.random.default_rng(R)
    table = rng.integers(0, 100, (C, omega, d)).astype(dtype)
    ids = rng.integers(0, C, R).astype(np.int32)
    got = packed_lookup(jnp.asarray(table), jnp.asarray(ids), interpret=True)
    want = ref.packed_lookup_ref(jnp.asarray(table), jnp.asarray(ids))
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_akpc_with_kernels_is_bit_identical():
    from repro.core import AKPCConfig, CostParams, run_akpc
    from repro.kernels import ops
    from repro.traces import paper_trace
    tr = paper_trace("netflix", n_requests=5000, seed=2)
    a = run_akpc(tr, AKPCConfig(params=CostParams(), t_cg=0.3, top_frac=1.0))
    b = run_akpc(tr, AKPCConfig(params=CostParams(), t_cg=0.3, top_frac=1.0,
                                crm_matmul=ops.crm_matmul,
                                pair_edges=ops.pair_edges))
    assert a.total == b.total
