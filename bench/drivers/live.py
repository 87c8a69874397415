"""Driver for ``LiveServingEngine.submit``/``drain``: the live serving path.

Traffic keys:

* ``loop``: ``"closed"`` submits arrival slices back to back, held only
  by the engine's own backpressure, for ``--seconds`` (or until the
  drawn stream ends); ``"open"`` submits each slice when it is due.
* ``slice``: requests per arrival slice (per ``submit`` call).
* ``warm_requests``: requests streamed in set-up, before the window, so
  that every program the window uses is compiled and loaded; drawn from
  ``WARM_SEED`` in every run.
* closed loop: ``requests``, the length of the drawn stream after the
  warm-up.
* open loop: ``profile`` (a load profile of ``gen.py`` over the window)
  and ``peak_req_per_s``, the offered rate at the profile's peak.  Trace
  time maps linearly onto wall time, so the window holds the whole
  profile and lasts ``--seconds``.

The engine keeps one session from the first warm-up request to the end
of the window; the reference replays that whole stream.
"""
from __future__ import annotations

import sys
import time

import numpy as np

import compare
import gen
import program


def _mean_level(profile: dict) -> float:
    x = np.linspace(0.0, 1.0, 100001)
    return float(np.trapezoid(gen.load_rate(profile, x), x))


#: seed of the warm-up prefix, the same in every run: the live engine
#: fixes its compiled chunk shapes from the first chunk it is given, so a
#: prefix drawn from --seed would compile anew in every run's set-up
WARM_SEED = 0


def draw(cell) -> dict:
    """The stream this cell submits (host only): a warm-up prefix drawn
    from ``WARM_SEED``, then the window's requests drawn from the seed
    and starting where the prefix ends."""
    cfg, tr = cell.cfg, cell.traffic
    n, m = cfg["catalog"]["n_items"], cfg["catalog"]["n_servers"]
    density = 1.0 / cfg["trace"]["time_per_request"]   # base req per time
    n_warm = int(tr["warm_requests"])
    warm = gen.trace(cfg["trace"], n, m, n_warm, n_warm / density,
                     gen.rng_for(WARM_SEED, 1))
    rng = gen.rng_for(cell.seed, 0)
    st = {"cfg": cfg, "tr": tr, "slice": int(tr["slice"]), "n_warm": n_warm}
    if tr["loop"] == "closed":
        R = int(tr["requests"])
        win = gen.trace(cfg["trace"], n, m, R, R / density, rng)
        st["due"] = None
    else:
        prof = tr["profile"]
        # trace time per wall second, so the profile's peak is offered at
        # peak_req_per_s; the window spans --seconds of wall time
        k = tr["peak_req_per_s"] / (density * float(
            gen.load_rate(prof, np.array([prof["load_peak"]]))[0]))
        span_t = cell.seconds * k
        R = int(round(density * span_t * _mean_level(prof)))
        win = gen.trace(dict(cfg["trace"], **prof), n, m, R, span_t, rng)
        st["due"] = np.concatenate([np.full(n_warm, -np.inf),
                                    win.times / k])  # wall s after start
    shift = max(float(warm.times[-1]), n_warm / density)
    st["log"] = gen.concat([warm, gen.Log(win.times + shift, win.servers,
                                          win.items, n, m)])
    return st


def setup(cell) -> dict:
    st = draw(cell)
    cfg, log, n_warm = cell.cfg, st["log"], st["n_warm"]
    n, m = cfg["catalog"]["n_items"], cfg["catalog"]["n_servers"]
    eng = program.LiveServingEngine(program.policy(cfg), n, m)
    sl = st["slice"]
    for lo in range(0, n_warm, sl):
        hi = min(lo + sl, n_warm)
        eng.submit(log.items[lo:hi], log.servers[lo:hi], log.times[lo:hi])
    eng.costs                       # blocks until the warm-up is priced
    st["eng"], st["submitted"] = eng, n_warm
    return st


def window(st: dict, seconds: float, span) -> dict:
    if st["due"] is None:
        return _closed(st, seconds, span)
    return _open(st, span)


def _closed(st, seconds, span) -> dict:
    eng, log, sl = st["eng"], st["log"], st["slice"]
    lo, end = st["n_warm"], log.n_requests
    t0 = time.perf_counter()
    stop = t0 + seconds
    with span("submit"):
        while lo < end and time.perf_counter() < stop:
            hi = min(lo + sl, end)
            eng.submit(log.items[lo:hi], log.servers[lo:hi],
                       log.times[lo:hi])
            lo = hi
    with span("drain"):
        eng.drain()
    elapsed = time.perf_counter() - t0
    if lo >= end:
        print("live: the drawn stream ran out before the window closed",
              file=sys.stderr)
    st["submitted"] = lo
    n = lo - st["n_warm"]
    return {"attempted": n, "elapsed_s": elapsed,
            "metrics": {"req_per_s": n / elapsed},
            "harness": {"requests": n}}


def _open(st, span) -> dict:
    eng, log, sl, due = st["eng"], st["log"], st["slice"], st["due"]
    n0, end = st["n_warm"], log.n_requests
    prev = sys.getswitchinterval()
    sys.setswitchinterval(2e-4)      # the completion thread wakes promptly
    watch = program.ChunkWatch(eng, n0)
    his = np.minimum(np.arange(n0 + sl, end + sl, sl), end)
    slice_due = due[his - 1]
    sub_at = np.empty(his.size)
    sub_s = np.empty(his.size)       # time spent inside each submit
    lo = n0
    t0 = time.perf_counter()
    try:
        with span("submit"):
            for s, hi in enumerate(his):
                when = t0 + slice_due[s]
                now = time.perf_counter()
                if when - now > 2e-3:
                    time.sleep(when - now - 1e-3)
                while time.perf_counter() < when:
                    pass
                t = time.perf_counter()
                eng.submit(log.items[lo:hi], log.servers[lo:hi],
                           log.times[lo:hi])
                sub_s[s] = time.perf_counter() - t
                sub_at[s] = t - t0
                watch.submitted(hi)
                lo = hi
        with span("drain"):
            watch.drain(end)
    finally:
        watch.close()
        sys.setswitchinterval(prev)
    elapsed = time.perf_counter() - t0
    st["submitted"] = end
    ch = np.array(watch.chunks, dtype=np.float64)
    ch[:, 2:] -= t0
    # the chunk holding each slice's last request
    idx = np.searchsorted(ch[:, 1], his - 1, side="right")
    lat = ch[idx, 3] - slice_due
    window_chunks = ch[ch[:, 0] >= n0]
    fill = window_chunks[:, 2] - due[window_chunks[:, 0].astype(np.int64)]
    probed = ch[:-1] if watch.flushed else ch    # the flush has no probe
    inflight = probed[:, 3] - probed[:, 2]
    n = end - n0
    worst = int(np.argmax(lat))
    print(f"open loop: {len(ch)} chunks; worst slice {lat[worst] * 1e3:.1f} "
          f"ms, due at {slice_due[worst]:.3f} s; longest in flight "
          f"{inflight.max(initial=0) * 1e3:.1f} ms, longest fill "
          f"{fill.max(initial=0) * 1e3:.1f} ms, longest submit "
          f"{sub_s.max() * 1e3:.1f} ms, generator at most "
          f"{(sub_at - slice_due).max() * 1e3:.2f} ms late", file=sys.stderr)
    return {
        "attempted": n, "elapsed_s": elapsed,
        "metrics": {"p50_latency_ms": float(np.percentile(lat, 50)) * 1e3,
                    "p95_latency_ms": float(np.percentile(lat, 95)) * 1e3},
        "harness": {
            "requests": n,
            "gen_late_ms": (sub_at - slice_due) * 1e3,
            "chunks": ch,
            "chunk_fill_ms": fill * 1e3,
            "chunk_inflight_ms": inflight * 1e3,
            "latency_ms": lat * 1e3}}


def answers(st: dict) -> list:
    """The whole stream this engine priced, against its drained result."""
    got = compare.program_answer(st.pop("eng").result())
    return [(st["log"].slice(0, st["submitted"]), st["cfg"]["costs"], got)]


def reference_inputs(cell, requests: int) -> list:
    """What a run compares, drawn without the program: the stream up to
    ``requests`` past the warm-up (closed loop) or whole (open loop)."""
    st = draw(cell)
    end = st["n_warm"] + requests if st["due"] is None else None
    return [(st["log"].slice(0, end), cell.cfg["costs"])]
