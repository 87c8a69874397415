"""Kernel choice rule + segment-reduction parity.

The Mosaic kernels engage on a TPU backend only, read at call time; the
host CGM and the numpy engine (the plain references) never touch them.
The per-server-dt segment scans run as jnp on every backend.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.cgm_jax import kernels_on_backend
from repro.kernels.segment_reduce import (
    seg_running_argmax_jnp,
    seg_running_argmax_ref,
    seg_running_max_jnp,
    seg_running_max_ref,
)


@pytest.mark.parametrize("backend,expect", [
    ("tpu", True), ("cpu", False), ("gpu", False)])
def test_kernel_choice_reads_backend_at_call_time(monkeypatch, backend,
                                                  expect):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert kernels_on_backend() is expect


def test_host_reference_stays_on_host_under_tpu_backend(monkeypatch):
    """The numpy replay (engine gather + host CGM) must not reach for a
    kernel even when the backend reports a TPU: each ``*_auto`` would
    then try to build a Mosaic kernel, which fails on this host."""
    from repro.core import CostParams, get_policy, run_policy
    from repro.traces import paper_trace

    tr = paper_trace("netflix", n_requests=3000, seed=5)
    want = run_policy(get_policy("akpc", params=CostParams(), t_cg=0.3,
                                 top_frac=1.0), tr).costs.total
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    got = run_policy(get_policy("akpc", params=CostParams(), t_cg=0.3,
                                top_frac=1.0), tr).costs.total
    assert got == want


# ---------------------------------------------------------------------------
# segment scans: jnp doubling scan == numpy oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("L,p_start,seed", [
    (1, 1.0, 0), (2, 0.5, 1), (17, 0.3, 2), (64, 0.1, 3),
    (257, 0.05, 4), (1024, 0.02, 5),
])
def test_segment_running_max_parity(L, p_start, seed):
    rng = np.random.default_rng(seed)
    v = rng.normal(size=L)
    s = rng.random(L) < p_start
    s[0] = True
    want = seg_running_max_ref(v, s)
    got_jnp = np.asarray(seg_running_max_jnp(jnp.asarray(v), jnp.asarray(s)))
    np.testing.assert_allclose(got_jnp, want.astype(got_jnp.dtype), rtol=0)


@pytest.mark.parametrize("L,p_start,seed", [
    (1, 1.0, 10), (31, 0.2, 11), (128, 0.05, 12), (1000, 0.01, 13),
])
def test_segment_running_argmax_parity(L, p_start, seed):
    rng = np.random.default_rng(seed)
    # duplicate values force the tie rule: LATEST index must win
    v = rng.integers(0, 5, L).astype(np.float64)
    s = rng.random(L) < p_start
    s[0] = True
    want_v, want_i = seg_running_argmax_ref(v, s)
    gv, gi = seg_running_argmax_jnp(jnp.asarray(v), jnp.asarray(s))
    np.testing.assert_allclose(np.asarray(gv), want_v)
    assert np.array_equal(np.asarray(gi), want_i)


def test_segment_argmax_tie_breaks_latest():
    v = np.array([2.0, 2.0, 2.0, 1.0])
    s = np.array([True, False, False, False])
    _, idx = seg_running_argmax_jnp(jnp.asarray(v), jnp.asarray(s))
    assert np.asarray(idx).tolist() == [0, 1, 2, 2]
