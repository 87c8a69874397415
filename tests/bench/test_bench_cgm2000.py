"""The ``cgm2000.sweep`` cell on the CPU at test size: a sound run is
correct with every grid point on the device CGM, a run with the timed
path broken and the float32 control are not, and the kernel cost
functions and readers count what they say.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q tests/bench
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import compare
import kernel_costs
import program
import run
import tracing
from drivers import cgm_sweep
from reference import akpc as reference

from repro.core import cgm_jax
from repro.core import cliques as cliques_mod
from repro.core import sweep as sweep_mod

CELL = "cgm2000.sweep"
SEED = 2**31 + 15
#: test size: a 240-item catalog over 40 servers, and a log dense enough
#: in time (3,000 requests over 3 time units) for two T_CG boundaries
CATALOG = {"n_items": 240, "n_servers": 40}
TRAFFIC = {"trace_requests": 3000, "traces": 1}
TIME_PER_REQUEST = 1e-3
V5E = run._json(f"{run.BENCH}/peaks.json")["devices"]["TPU v5 lite"]


@pytest.fixture
def small(monkeypatch):
    """The cell at test size, with no compiled scan or padded dims
    carried in or out."""
    init = run.Cell.__init__

    def cell(self, *a, **k):
        init(self, *a, **k)
        self.traffic = {**self.traffic, **TRAFFIC}
        self.cfg = {**self.cfg, "catalog": CATALOG, "trace": {
            **self.cfg["trace"], "time_per_request": TIME_PER_REQUEST}}

    monkeypatch.setattr(run.Cell, "__init__", cell)
    monkeypatch.setattr(sweep_mod, "_COHORT_DIMS", {})
    cgm_jax._compiled_cgm_replay.cache_clear()
    yield
    cgm_jax._compiled_cgm_replay.cache_clear()


def _run_cell(capsys):
    rc = run.run(["--workload", CELL, "--seed", str(SEED), "--seconds",
                  "0.5"], find=lambda chips: jax.devices())
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _engine_stats(monkeypatch) -> list:
    """The ``stats`` of every ``SweepEngine.run`` call, in order."""
    calls = []
    sw = program.SweepEngine.run

    def spy(self, *a, **k):
        out = sw(self, *a, **k)
        calls.append(dict(self.stats))
        return out

    monkeypatch.setattr(program.SweepEngine, "run", spy)
    return calls


def test_grid_is_the_three_axes_through_the_table_ii_point():
    cfg = run._json(f"{run.BENCH}/configs/akpc-netflix-2k.json")
    tr = run._json(f"{run.BENCH}/traffic/sweep-fig7-axes.json")
    pts = cgm_sweep.grid(cfg, tr)
    assert len(pts) == 15
    assert {"theta": 0.2, "gamma": 0.85, "omega": 5} in pts
    for p in pts:   # one axis off the centre at a time
        off = [k for k in cgm_sweep.AXES if p[k] != cfg["costs"][k]]
        assert len(off) <= 1


def test_sound_run_is_correct_with_every_point_on_the_device(
        monkeypatch, capsys, small):
    calls = _engine_stats(monkeypatch)
    before = cliques_mod.CGM_CALLS
    out = _run_cell(capsys)
    assert out["correct"] is True, out["checks"]
    assert cliques_mod.CGM_CALLS == before          # no host clique walk
    assert calls
    for st in calls:
        assert st["cgm_lanes"] == 15 and st["cgm_host_points"] == 0
        assert st["cgm_boundaries"] == 2 * 15
        assert st["cgm_merge_space"] == 2 * CATALOG["n_items"]
        assert st["cgm_merge_iters"] > 0


def _boundary_unchanged(monkeypatch):
    """Every T_CG boundary hands back the carry it was given."""
    monkeypatch.setattr(
        cgm_jax, "_cgm_boundary",
        lambda carry, *a, **k: (carry, jnp.zeros((), jnp.int32)))


def _answer_altered(monkeypatch):
    """The transfer cost comes out 1e-7 high where it is produced."""
    sw = program.SweepEngine.run

    def sweep(self, *a, **k):
        out = sw(self, *a, **k)
        for res in out:
            res.costs.transfer *= 1 + 1e-7
        return out

    monkeypatch.setattr(program.SweepEngine, "run", sweep)


@pytest.mark.parametrize("fault", [_boundary_unchanged, _answer_altered])
def test_broken_timed_path_is_not_correct(monkeypatch, capsys, small, fault):
    fault(monkeypatch)
    out = _run_cell(capsys)
    assert out["correct"] is False, out["checks"]


def test_float32_control_is_not_correct(small):
    cell = run.Cell(run._json(f"{run.ROOT}/BENCHMARK.json"), CELL, SEED, 1.0)
    pairs = [(reference.run(log, costs, cell.cfg["policy"]),
              reference.run(log, costs, cell.cfg["policy"], np.float32))
             for log, costs in cgm_sweep.reference_inputs(cell, 0)]
    assert len(pairs) == 15
    ok, checks = compare.judge(pairs, cell.cfg["guarantee"])
    assert not ok, checks
    assert checks["cost_rel_dev"]["value"] > 10 * cell.cfg["guarantee"][
        "cost_rel_tol"]


def test_kernel_costs_match_a_hand_count():
    """h = 2 hot items, wcap = 3 window rows, scap = 4 merge slots, 5
    lane-boundaries; a multiply-add counts 2 operations, a value 4
    bytes."""
    # H^T H: 2 x 2 outputs of 3 multiply-adds; read H (3 x 2), write 2 x 2
    assert kernel_costs.crm_update(h=2, wcap=3, lanes=5) == (
        5 * 4 * 3 * 2, 5 * 4 * (6 + 4))
    # T = M A: 4 x 2 outputs of 2 multiply-adds; X = T M^T: 4 x 4 of 2;
    # read M (4 x 2) and A (2 x 2), write X (4 x 4)
    assert kernel_costs.clique_pair_edges(h=2, scap=4, lanes=5) == (
        5 * (8 * 2 * 2 + 16 * 2 * 2), 5 * 4 * (8 + 4 + 16))
    # D: two additions per entry of 4 x 4; read X, within (row and column)
    # and sizes (row and column), write D
    assert kernel_costs.merge_density(scap=4, lanes=5) == (
        5 * 2 * 16, 5 * 4 * (16 + 4 * 4 + 16))


def _reduced(op_s: dict) -> tracing.Reduced:
    return tracing.Reduced(window_s=1.0, busy_s=1.0, op_s=op_s,
                           module_s={}, idle_gaps=[])


def test_roofline_readers_read_the_kernels_calls(monkeypatch):
    mosaic = 'custom-call(f32[8] %p), custom_call_target="tpu_custom_call"'
    op_s = {f"%crm_update.1 = f32[15,2048,2048] {mosaic}": 0.15,
            f"%crm_update.4 = f32[15,2048,2048] {mosaic}": 0.05,
            "%crm_update.2 = f32[15,2048,2048] fusion(f32[8] %p)": 9.0,
            f"%clique_pair_edges.2 = f32[4] {mosaic}": 0.1,
            f"%merge_density.1 = f32[4] {mosaic}": 0.1}
    harness = {"cgm_hot": 2000, "cgm_merge_space": 4000,
               "cgm_window_rows": 22272, "cgm_boundaries": 60}
    monkeypatch.setattr(kernel_costs, "device_peaks", lambda: V5E)
    r = run.Readings(_reduced(op_s), harness)
    # crm_update: 2 * 22272 * 2000^2 * 60 operations at 197e12/s bound it
    # (5.43e-2 s against 1.42e-2 s for its bytes), over 0.2 s of calls
    want = 100 * (2 * 22272 * 2000**2 * 60 / 197e12) / 0.2
    assert run._load_metric("crm_update_roofline")(r) == pytest.approx(want)
    for name in ("clique_pair_edges_roofline", "merge_density_roofline"):
        assert 0 < run._load_metric(name)(r) <= 100
    # merge_density is bound by its bytes: 8 * 4000^2 * 60 at 819e9/s
    assert run._load_metric("merge_density_roofline")(r) == pytest.approx(
        100 * (4 * (2 * 4000**2 + 4 * 4000) * 60 / 819e9) / 0.1)
    # a program without the engine's stats, or a trace without the
    # kernel, gives nothing to read
    bare = run.Readings(_reduced(op_s), {"requests": 1})
    assert run._load_metric("crm_update_roofline")(bare) is None
    none = run.Readings(_reduced({}), harness)
    assert run._load_metric("merge_density_roofline")(none) is None


def test_lane_share_reads_the_engine_counters():
    read = run._load_metric("cgm_lane_share")
    assert read(run.Readings(None, {"cgm_lanes": 26, "points": 30})) == \
        pytest.approx(100 * 26 / 30)
    assert read(run.Readings(None, {"points": 30})) is None
