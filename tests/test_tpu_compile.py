"""Compile rehearsals for one TPU v5e chip, without the chip.

The TPU compiler is installed with JAX and compiles for a chip that is
described rather than attached, so these tests catch what Mosaic or XLA
would refuse on the chip (64-bit values in a kernel, an index map of the
wrong width, a block that overflows VMEM) at no chip time.  Everything
compiles at the widths the program runs at and under ``enable_x64``, as
the replay scans trace it.  Nothing runs: results and times come only
from a chip run (``chip_smoke.py``).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the suite runs under
several workers.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import (
    CacheEnvironment, CostParams, cgm_jax, get_policy,
)
from repro.core import engine_jax as ej
from repro.core.engine_jax import JaxReplayEngine, build_schedule
from repro.kernels.clique_density import clique_pair_edges
from repro.kernels.crm_update import crm_update
from repro.kernels.merge_step import merge_density
from repro.traces import SynthConfig, paper_trace, synth_trace

H_BOUND = cgm_jax.MAX_DEVICE_CGM_HOT


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture
def on_tpu(monkeypatch):
    """Steer the call-time kernel choice (``*_auto``) to Mosaic."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _sds(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                       sharding=sharding), tree)


def _compile(fn, *args):
    with jax.enable_x64(True):
        return jax.jit(fn).lower(*args).compile()


def _mosaic_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def test_crm_update_compiles(one_chip):
    c = _compile(crm_update, jax.ShapeDtypeStruct(
        (8192, H_BOUND), jnp.float32, sharding=one_chip))
    assert _mosaic_calls(c) >= 1


@pytest.mark.parametrize("h", [1024, H_BOUND])
def test_clique_pair_edges_compiles(one_chip, h):
    """The merge space is (2h, h) against an (h, h) CRM; the tiled
    kernel keeps its VMEM bounded up to the routing bound."""
    c = _compile(
        clique_pair_edges,
        jax.ShapeDtypeStruct((2 * h, h), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((h, h), jnp.float32, sharding=one_chip))
    assert _mosaic_calls(c) == 2


def test_merge_density_compiles(one_chip):
    S = 2 * H_BOUND
    c = _compile(
        merge_density,
        jax.ShapeDtypeStruct((S, S), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((S,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip))
    assert _mosaic_calls(c) == 1


def _replay_args(cost_model, env=None):
    """A Table II (60 items x 600 servers) replay schedule and its
    spec/state inputs, as ``run_schedule`` hands them to the scan."""
    tr = paper_trace("netflix", n_requests=60_000, seed=0)
    params = CostParams()
    env = CacheEnvironment.resolve(env, tr, params)
    pol = get_policy("akpc", env=env, cost_model=cost_model, t_cg=0.3,
                     top_frac=1.0)
    pol.bind(tr.n, tr.m)
    jeng = JaxReplayEngine(tr.n, tr.m, pol.params, env=env,
                           cost_model=cost_model)
    eng = jeng.engine
    sched = build_schedule(eng.state.partition, tr, pol.on_window, 0.3,
                           model=eng.model, env=eng.env)
    E0, a0 = ej.fresh_state_arrays(tr.n, tr.m)
    init = (E0, a0, np.zeros(ej.N_ACC, np.float64))
    return sched, jeng, init


@pytest.mark.parametrize("cost_model", ["table1", "heterogeneous"])
def test_replay_scan_compiles(one_chip, cost_model):
    """Constant dt (table1) and per-server dt (heterogeneous, the
    segmented jnp scans over f64 expiries)."""
    env = None
    if cost_model == "heterogeneous":
        env = CacheEnvironment.skewed(60, 600, price_sigma=1.0,
                                      size_sigma=0.75)
    sched, jeng, init = _replay_args(cost_model, env)
    assert sched.const_dt == (cost_model == "table1")
    fn = ej._compiled_replay(jeng._statics, "requested", sched.const_dt,
                             False)
    with jax.enable_x64(True):
        c = fn.lower(_sds(jeng._spec, one_chip), _sds(init, one_chip),
                     _sds(sched.xs, one_chip)).compile()
    assert c.memory_analysis().temp_size_in_bytes < 16 << 30


def test_fused_cgm_scan_with_kernels_compiles(one_chip, on_tpu):
    """The in-scan CGM boundary with the Mosaic kernels, at the fig7
    n = 2000 catalog."""
    tr = synth_trace(SynthConfig(
        kind="spotify", n_items=2000, n_servers=20, n_requests=3000,
        t_max=20.0, bundle_cover=1.0, bundle_zipf=0.7, seed=0))
    t_cg = float(tr.times[-1] - tr.times[0]) / 12
    pol = get_policy("akpc", t_cg=t_cg, top_frac=0.5)
    pol.bind(tr.n, tr.m)
    env = CacheEnvironment.resolve(None, tr, pol.params)
    jeng = JaxReplayEngine(tr.n, tr.m, pol.params, env=env)
    sched = cgm_jax.build_cgm_schedule(
        tr, t_cg, uses_sizes=False, hot_dims=cgm_jax.policy_hot_dims(pol))
    cspec = cgm_jax.cgm_spec(pol.config, pol.config.params, tr.n)
    carry0 = cgm_jax.init_cgm_carry(
        jeng.engine.state, None, None, n=tr.n, m=tr.m, uses_sizes=False,
        item_sizes=None, schedule=sched)
    gcap, full_merge = cgm_jax.cgm_loop_statics(
        cspec, carry0, enable_split=True, enable_acm=True)
    fn = cgm_jax._compiled_cgm_replay(
        jeng._statics, "requested", False, True, True, True, True, gcap,
        full_merge, False)
    with jax.enable_x64(True):
        c = fn.lower(
            _sds(jeng._spec, one_chip), _sds(cspec, one_chip),
            _sds(carry0, one_chip), _sds(sched.xs, one_chip),
            jax.ShapeDtypeStruct((tr.n,), jnp.float64,
                                 sharding=one_chip)).compile()
    assert _mosaic_calls(c) >= 3
