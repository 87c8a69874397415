"""What decides ``correct``, on the CPU at test sizes: the float32
precision control, and whole runs of every cell, sound and with the timed
path broken underneath, each of which must come out not correct.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q tests/bench
"""
import dataclasses
import json

import numpy as np
import pytest

import compare
import gen
import program
import run
from reference import akpc as reference

from repro.core import cgm_jax, engine_jax
from repro.serving import live


def _cfg(name):
    return run._json(f"{run.BENCH}/configs/{name}.json")


def _log(cfg, n_requests, seed=7):
    c = cfg["catalog"]
    return gen.trace(cfg["trace"], c["n_items"], c["n_servers"], n_requests,
                     n_requests * cfg["trace"]["time_per_request"],
                     gen.rng_for(seed, 0))


def test_float32_control_is_not_correct():
    cfg = _cfg("akpc-netflix-t2")
    log = _log(cfg, 60_000)
    ref = reference.run(log, cfg["costs"], cfg["policy"])
    f32 = reference.run(log, cfg["costs"], cfg["policy"], np.float32)
    ok, checks = compare.judge([(ref, f32)], cfg["guarantee"])
    assert not ok, checks
    assert checks["cost_rel_dev"]["value"] > 10 * cfg["guarantee"][
        "cost_rel_tol"]
    ok, checks = compare.judge([(ref, ref)], cfg["guarantee"])
    assert ok and all(c["value"] == 0 for c in checks.values())


# -- whole runs at test sizes, with the timed path broken -------------------

SMALL = {
    "live-closed": {"warm_requests": 2000, "requests": 6000},
    "sweep-alpha-rho": {"trace_requests": 3000, "traces": 1,
                        "alpha": [0.6, 1.0, 2], "rho": [1.0, 6.0, 2]},
}
CELLS = [w["name"] for w in run._json(
    f"{run.ROOT}/BENCHMARK.json")["workloads"]]


def _run_cell(monkeypatch, capsys, workload):
    init = run.Cell.__init__

    def small(self, *a, **k):
        init(self, *a, **k)
        self.traffic = {**self.traffic, **SMALL[self.entry["traffic"]]}

    monkeypatch.setattr(run.Cell, "__init__", small)
    import jax

    rc = run.run(["--workload", workload, "--seed", str(2**31 + 11),
                  "--seconds", "0.5"], find=lambda chips: jax.devices())
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _clear_step_caches():
    live._compiled_cgm_live_step.cache_clear()
    live._compiled_live_step.cache_clear()
    engine_jax._compiled_replay.cache_clear()
    cgm_jax._compiled_cgm_replay.cache_clear()


@pytest.fixture
def fresh_steps():
    _clear_step_caches()
    yield
    _clear_step_caches()


def _state_unchanged(monkeypatch):
    """Every scan step hands back the carry it was given."""
    cgm_scan, scan = cgm_jax._cgm_replay_impl, engine_jax._replay_impl

    def cgm_frozen(spec, cspec, init, xs, item_sizes, **kw):
        return init, cgm_scan(spec, cspec, init, xs, item_sizes, **kw)[1]

    def frozen(spec, init, xs, **kw):
        scan(spec, init, xs, **kw)
        return init

    monkeypatch.setattr(cgm_jax, "_cgm_replay_impl", cgm_frozen)
    monkeypatch.setattr(engine_jax, "_replay_impl", frozen)


def _half_the_batch(monkeypatch):
    """Every entry point prices only the first half of what it is given."""
    sub = live.LiveServingEngine.submit

    def submit(self, items, servers, times):
        h = max(1, len(times) // 2)
        return sub(self, items[:h], servers[:h], times[:h])

    monkeypatch.setattr(live.LiveServingEngine, "submit", submit)
    sw = program.SweepEngine.run

    def sweep(self, points, *a, **k):
        pts = [dataclasses.replace(p, trace=p.trace.slice(
            0, p.trace.n_requests // 2)) for p in points]
        return sw(self, pts, *a, **k)

    monkeypatch.setattr(program.SweepEngine, "run", sweep)


def _answer_altered(monkeypatch):
    """The transfer cost comes out 1e-7 high where it is produced."""
    sync = live.LiveServingEngine._sync_costs

    def sync_costs(self):
        c = sync(self)
        c.transfer *= 1 + 1e-7
        return c

    monkeypatch.setattr(live.LiveServingEngine, "_sync_costs", sync_costs)
    sw = program.SweepEngine.run

    def sweep(self, *a, **k):
        out = sw(self, *a, **k)
        for res in out:
            res.costs.transfer *= 1 + 1e-7
        return out

    monkeypatch.setattr(program.SweepEngine, "run", sweep)


FAULTS = {"state_unchanged": _state_unchanged,
          "half_the_batch": _half_the_batch,
          "answer_altered": _answer_altered}


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(monkeypatch, capsys, fresh_steps, workload):
    out = _run_cell(monkeypatch, capsys, workload)
    assert out["correct"] is True, out["checks"]
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", CELLS)
def test_broken_timed_path_is_not_correct(monkeypatch, capsys, fresh_steps,
                                          workload, fault):
    FAULTS[fault](monkeypatch)
    out = _run_cell(monkeypatch, capsys, workload)
    assert out["correct"] is False, out["checks"]
