"""Program spans, counters and device scopes (``repro.obs``).

Contracts under test, each on a real CPU profiler trace:

* live engine — every dispatched chunk has ``akpc.live.pack``, ``.put``
  and ``.launch`` spans, in that order, with ``chunk`` and ``requests``
  stats; a ring past its depth records ``akpc.live.ring_wait`` on the
  oldest chunk; ``LiveServingEngine.stats`` agrees with the spans;
* sweep — one ``akpc.sweep.call`` holds the prepare, schedule, stage and
  collect phases, and host clique generation (``akpc.cgm.window``) sits
  inside the schedule build that asked for it; points whose traces are
  equal in content share one schedule (``shared`` on the call span);
* ``ServeFuture.done()`` follows the chunk that holds the submit's last
  request, not the whole ring;
* the fused CGM step and the replay step carry their ``jax.named_scope``
  names in op metadata and keep the program name ``jit_step``.
"""
import contextlib
import time

import jax
import pytest

from repro import obs
from repro.core import CostParams, SweepEngine, SweepPoint, get_policy
from repro.serving import LiveServingEngine, live
from repro.traces import SynthConfig, Trace, synth_trace

T_CG = 0.73


def _trace(n_requests=1200, seed=3):
    return synth_trace(SynthConfig(
        kind="netflix", n_items=60, n_servers=12, n_requests=n_requests,
        t_max=n_requests / 130.0, bundle_cover=1.0, bundle_zipf=0.7,
        seed=seed))


def _policy(**params):
    return get_policy("akpc", params=CostParams(**params), t_cg=T_CG,
                      top_frac=1.0)


def _submit(eng, tr, lo, hi):
    return eng.submit(tr.items[lo:hi], tr.servers[lo:hi], tr.times[lo:hi])


@contextlib.contextmanager
def _recording(path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(path), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _by_name(spans) -> dict:
    out = {}
    for name, s, d, stats in spans:
        out.setdefault(name[len(obs.PREFIX):], []).append((s, s + d, stats))
    return out


def _inside(inner, outers) -> bool:
    return any(o[0] <= inner[0] and inner[1] <= o[1] for o in outers)


# ---------------------------------------------------------------------------
# live engine
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("cgm", ["auto", "off"])
def test_live_spans_match_stats(tmp_path, cgm):
    tr = _trace()
    eng = LiveServingEngine(_policy(), tr.n, tr.m, chunk_size=128, ring=1,
                            cgm=cgm)
    assert eng._cgm == (cgm == "auto")
    with _recording(tmp_path):
        for lo in range(0, tr.n_requests, 100):
            _submit(eng, tr, lo, min(lo + 100, tr.n_requests))
        eng.drain()
    sp = _by_name(obs.read(str(tmp_path)))
    st = eng.stats
    chunks = st["chunks"]
    assert chunks == -(-tr.n_requests // 128)
    assert st["requests"] == tr.n_requests
    for name in ("live.pack", "live.put", "live.launch"):
        assert [s[2]["chunk"] for s in sp[name]] == list(range(chunks))
        assert sum(s[2]["requests"] for s in sp[name]) == tr.n_requests
    for pack, put, launch in zip(sp["live.pack"], sp["live.put"],
                                 sp["live.launch"]):
        assert pack[1] <= put[0] and put[1] <= launch[0]
    assert sum(s[2]["bytes"] for s in sp["live.put"]) == st["h2d_bytes"] > 0
    # ring of one: every chunk after the first waits on its predecessor
    waits = sp["live.ring_wait"]
    assert len(waits) == st["ring_waits"] == chunks - 1
    assert [w[2]["waits_on"] for w in waits] == [
        w[2]["chunk"] - 1 for w in waits]
    assert st["ring_wait_s"] > 0.0
    assert len(sp.get("live.grow", [])) == st["carry_grows"]
    assert st["compiles"] == eng.compiles
    assert sum(s[2]["windows"] for s in sp["live.pack"]) \
        == eng.policy.n_windows > 0
    assert [s[2]["call"] for s in sp["live.sync"]] == ["drain"]
    # host clique generation only where the fused scan is off
    host_windows = sp.get("cgm.window", [])
    if cgm == "auto":
        assert not host_windows
    else:
        assert len(host_windows) == eng.policy.n_windows
        assert all(_inside(w, sp["live.pack"]) for w in host_windows)
        assert all(w[2]["hot"] > 0 for w in host_windows)


def test_live_stats_without_profiler():
    tr = _trace(n_requests=400)
    eng = LiveServingEngine(_policy(), tr.n, tr.m, chunk_size=128, ring=2)
    _submit(eng, tr, 0, 400)
    eng.drain()
    st = eng.stats
    assert set(st) == {"chunks", "requests", "ring_waits", "ring_wait_s",
                       "h2d_bytes", "carry_grows", "compiles"}
    assert (st["chunks"], st["requests"], st["ring_waits"]) == (4, 400, 2)


def test_future_done_follows_its_chunk():
    tr = _trace(n_requests=600)
    eng = LiveServingEngine(_policy(), tr.n, tr.m, chunk_size=128, ring=8)
    f0 = _submit(eng, tr, 0, 128)           # fills and dispatches chunk 0
    f1 = _submit(eng, tr, 128, 178)         # buffered
    assert eng.in_flight == 1 and eng.pending == 50
    deadline = time.monotonic() + 120
    while not f0.done() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert f0.done()                        # while chunk 0 is still
    assert eng.in_flight == 1               # on the ring
    assert not f1.done()
    _submit(eng, tr, 178, 300)              # dispatches chunk 1
    eng._probes[-1].block_until_ready()
    assert f1.done()
    f2 = _submit(eng, tr, 300, 310)
    assert not f2.done()
    eng.drain()
    assert f0.done() and f1.done() and f2.done()


# ---------------------------------------------------------------------------
# sweep
# ---------------------------------------------------------------------------
def _second_trace(tr, how):
    """The second point's trace: ``tr`` itself, a fresh ``Trace`` over its
    arrays, one over copies of them, or a copy with one request moved to
    another server."""
    if how == "same_object":
        return tr
    if how == "fresh_trace":
        return Trace(times=tr.times, servers=tr.servers, items=tr.items,
                     n=tr.n, m=tr.m)
    servers = tr.servers.copy()
    if how == "one_request_changed":
        servers[400] = (servers[400] + 1) % tr.m
    return Trace(times=tr.times.copy(), servers=servers,
                 items=tr.items.copy(), n=tr.n, m=tr.m)


@pytest.mark.parametrize("how, n_sched", [
    ("same_object", 1), ("fresh_trace", 1), ("copied_arrays", 1),
    ("one_request_changed", 2)])
def test_sweep_spans(tmp_path, how, n_sched):
    """Schedules are shared by trace content: equal logs share one,
    a log that differs in one request gets its own."""
    tr = _trace(n_requests=800)

    def point(t, alpha):
        return SweepPoint("akpc", t, dict(
            params=CostParams(alpha=alpha), t_cg=T_CG, top_frac=1.0))

    eng = SweepEngine()
    with _recording(tmp_path):
        res = eng.run([point(tr, 0.5), point(_second_trace(tr, how), 0.9)])
    assert len(res) == 2
    assert eng.last_n_schedules == n_sched
    sp = _by_name(obs.read(str(tmp_path)))
    (call,) = sp["sweep.call"]
    assert call[2] == {"points": 2, "schedules": n_sched,
                       "shared": 2 - n_sched, "lanes": 2, "groups": 1}
    (prep,) = sp["sweep.prepare"]
    assert prep[2] == {"points": 2}
    scheds = sp["sweep.schedule"]
    assert len(scheds) == n_sched
    assert all(s[2]["steps"] > 0 and s[2]["events"] > 0 for s in scheds)
    windows = sp["cgm.window"]
    assert len(windows) == n_sched * res[0].n_windows > 0
    assert all(_inside(w, scheds) for w in windows)
    (stage,) = sp["sweep.stage"]
    assert stage[2]["lanes"] == 2 and stage[2]["bytes"] > 0
    (collect,) = sp["sweep.collect"]
    assert len(sp["sweep.wait"]) == 1
    assert _inside(sp["sweep.wait"][0], [collect])
    phases = [prep, *scheds, stage, collect]
    assert all(_inside(p, [call]) for p in phases)
    assert [p[0] for p in phases] == sorted(p[0] for p in phases)


# ---------------------------------------------------------------------------
# device scopes
# ---------------------------------------------------------------------------
def _lowered_live_step(monkeypatch, cgm):
    """The text of the live engine's jitted step, lowered with the shapes
    of its first call."""
    name = "_compiled_cgm_live_step" if cgm == "auto" else \
        "_compiled_live_step"
    real = getattr(live, name)
    seen = {}

    def spy(*key):
        fn = real(*key)

        def call(*args):
            seen.setdefault("args", jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args))
            seen["fn"] = fn
            return fn(*args)
        return call

    monkeypatch.setattr(live, name, spy)
    tr = _trace(n_requests=300)
    eng = LiveServingEngine(_policy(), tr.n, tr.m, chunk_size=128, cgm=cgm)
    _submit(eng, tr, 0, 300)
    eng.drain()
    with jax.enable_x64(True):
        low = seen["fn"].lower(*seen["args"])
    return low.as_text().splitlines()[0], low.as_text(debug_info=True)


def test_fused_cgm_step_carries_scopes(monkeypatch):
    head, text = _lowered_live_step(monkeypatch, "auto")
    assert head.startswith("module @jit_step ")
    for scope in ("cgm_boundary/", "/crm/", "/adjust/", "/split/",
                  "/merge/", "/install/", "window_accumulate/",
                  "event_step/"):
        assert scope in text, scope


def test_replay_step_carries_scopes(monkeypatch):
    head, text = _lowered_live_step(monkeypatch, "off")
    assert head.startswith("module @jit_step ")
    assert "install/" in text and "event_step/" in text


def test_read_without_trace_raises(tmp_path):
    with obs.span("idle", chunk=0):       # no trace active: a no-op
        pass
    with pytest.raises(FileNotFoundError):
        obs.read(str(tmp_path))
