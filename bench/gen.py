"""The benchmark's one request generator: a copy of the program's
synthetic trace recipe, kept here so that no change to the program can
move the yardstick.

``trace(shape, n_requests, t_max, rng)`` draws a time-sorted request log
with the session model of ``src/repro/traces/synthetic.py``: Zipf bundle
popularity, per-server bundle affinity, sessions of several items of one
bundle, multi-item requests up to ``d_max`` and a small share of noise.
``shape`` is the configuration's ``trace`` group, optionally with a load
profile (``load_profile``, ``load_strength``, ``load_peak``,
``load_width``) from a traffic mix.  Arrival times follow the profile;
the content of the requests does not depend on it.

Unlike the original, the draws come from a ``numpy.random.Generator``
made from the run's seed (any whole number), and item sizes are always
unit (the configurations price unit items).
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Log:
    """A time-sorted request log: the reference's input and, wrapped in
    the program's own container, the program's."""

    times: np.ndarray      # (R,) float64, non-decreasing
    servers: np.ndarray    # (R,) int32 in [0, m)
    items: np.ndarray      # (R, d_max) int32, -1 padded
    n: int
    m: int

    @property
    def n_requests(self) -> int:
        return int(self.times.shape[0])

    def slice(self, lo: int, hi: int) -> "Log":
        return Log(self.times[lo:hi], self.servers[lo:hi],
                   self.items[lo:hi], self.n, self.m)


def concat(logs: list[Log]) -> Log:
    """Logs one after the other (times must already be ordered)."""
    return Log(np.concatenate([g.times for g in logs]),
               np.concatenate([g.servers for g in logs]),
               np.concatenate([g.items for g in logs]),
               logs[0].n, logs[0].m)


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """One independent generator per (seed, stream...)."""
    return np.random.default_rng([int(seed) % (1 << 64), *stream])


def load_rate(shape: dict, x: np.ndarray) -> np.ndarray:
    """Arrival-rate profile at x = t / t_max (mean level about 1)."""
    prof = shape.get("load_profile", "stationary")
    if prof == "stationary":
        return np.ones_like(x)
    if prof == "flash_crowd":
        w = max(shape["load_width"], 1e-6)
        return 1.0 + shape["load_strength"] * np.exp(
            -0.5 * ((x - shape["load_peak"]) / w) ** 2)
    raise ValueError(f"unknown load_profile: {prof!r}")


def _warp(shape: dict, u: np.ndarray, t_max: float) -> np.ndarray:
    """Uniform draws -> arrival times under ``load_rate`` (inverse CDF)."""
    if shape.get("load_profile", "stationary") == "stationary":
        return u * t_max
    grid = np.linspace(0.0, 1.0, 4097)
    lam = load_rate(shape, grid)
    cdf = np.concatenate([
        [0.0], np.cumsum(0.5 * (lam[1:] + lam[:-1]) * np.diff(grid))])
    cdf /= cdf[-1]
    return np.interp(u, cdf, grid) * t_max


def _zipf_choice(rng, n: int, s: float, size: int) -> np.ndarray:
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** s
    w /= w.sum()
    return rng.choice(n, size=size, p=w)


def trace(shape: dict, n_items: int, n_servers: int, n_requests: int,
          t_max: float, rng: np.random.Generator) -> Log:
    """Draw ``n_requests`` requests over ``[0, t_max]`` (see module doc)."""
    lo, hi = shape["bundle_size"]
    d_max = shape["d_max"]
    mean_len = shape["mean_session_len"]
    # latent bundles over the catalog
    covered = int(n_items * shape["bundle_cover"])
    sizes: list[int] = []
    total = 0
    while total < covered:
        sz = int(rng.integers(lo, hi + 1))
        sizes.append(sz)
        total += sz
    starts = np.cumsum([0] + sizes[:-1])
    sizes_a = np.array(sizes)
    starts = starts[starts + sizes_a <= n_items]
    sizes_a = sizes_a[: len(starts)]
    n_bundles = len(starts)

    # sessions
    n_sess = int(n_requests / mean_len * 1.3) + 8
    sess_len = rng.geometric(1.0 / mean_len, size=n_sess)
    sess_len = np.clip(sess_len, 1, 4 * int(mean_len))
    n_sess = int(np.searchsorted(np.cumsum(sess_len), n_requests) + 1)
    sess_len = sess_len[:n_sess]
    R = int(sess_len.sum())

    sess_server = _zipf_choice(rng, n_servers, shape["server_zipf"], n_sess)
    bz = shape["bundle_zipf"]
    aff = shape["server_affinity"]
    if aff > 0 and n_bundles > aff:
        wb = 1.0 / np.arange(1, n_bundles + 1) ** bz
        wb /= wb.sum()
        prefs = np.stack([rng.choice(n_bundles, size=aff, replace=False,
                                     p=wb) for _ in range(n_servers)])
        sess_bundle = prefs[sess_server, rng.integers(0, aff, size=n_sess)]
        escape = rng.random(n_sess) < shape["p_affinity_escape"]
        n_esc = int(escape.sum())
        if n_esc:
            sess_bundle[escape] = _zipf_choice(rng, n_bundles, bz, n_esc)
    else:
        sess_bundle = _zipf_choice(rng, n_bundles, bz, n_sess)
    sess_start = _warp(shape, rng.uniform(0.0, 1.0, size=n_sess), t_max)

    req_sess = np.repeat(np.arange(n_sess), sess_len)
    req_bundle = sess_bundle[req_sess]
    servers = sess_server[req_sess].astype(np.int32)
    cum = np.cumsum(rng.exponential(shape["intra_gap"], size=R))
    first = np.cumsum(sess_len) - sess_len
    times = sess_start[req_sess] + (cum - np.repeat(cum[first], sess_len))

    # items: random subsets of the session's bundle
    b_start = starts[req_bundle]
    b_size = sizes_a[req_bundle]
    n_it = np.ones(R, dtype=np.int64)
    multi = rng.random(R) < shape["p_multi"]
    n_it[multi] = rng.integers(2, d_max + 1, size=int(multi.sum()))
    n_it = np.minimum(n_it, b_size)
    max_b = int(sizes_a.max())
    u = rng.random((R, max_b))
    u[np.arange(max_b)[None, :] >= b_size[:, None]] = np.inf
    pick = np.argsort(u, axis=1)[:, :d_max]
    items = (b_start[:, None] + pick).astype(np.int32)
    items[np.arange(d_max)[None, :] >= n_it[:, None]] = -1

    # noise, then de-duplicate within a request
    noise = (rng.random(items.shape) < shape["p_noise"]) & (items >= 0)
    items[noise] = rng.integers(0, n_items, size=int(noise.sum())).astype(
        np.int32)
    srt = np.sort(items, axis=1)[:, ::-1]
    dup = np.zeros_like(srt, dtype=bool)
    dup[:, 1:] = (srt[:, 1:] == srt[:, :-1]) & (srt[:, 1:] >= 0)
    srt[dup] = -1
    items = np.ascontiguousarray(np.sort(srt, axis=1)[:, ::-1])

    order = np.argsort(times, kind="stable")[:n_requests]
    return Log(times[order], servers[order], items[order], n_items,
               n_servers)
