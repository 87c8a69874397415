"""Kernel benchmarks: the Mosaic kernels on a TPU vs jnp oracles.

Runs only where JAX's default backend is a TPU: the kernels compile to
Mosaic there and nowhere else, and a CPU timing of them would say
nothing about the chip.  The structural metrics (DMA descriptor counts,
bytes per descriptor, MXU tile counts) come from shapes.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from .common import emit, save_json
from repro.kernels import ops, ref
from repro.kernels.packed_lookup import packed_lookup


def _time(fn, *args, reps=3):
    fn(*args)                                 # compile/warm
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    return (time.perf_counter() - t0) / reps, out


def main() -> list[tuple]:
    if jax.default_backend() != "tpu":
        raise SystemExit(
            f"kernel_bench needs a TPU backend, found "
            f"{jax.default_backend()!r}")
    rng = np.random.default_rng(0)
    rows, payload = [], {}

    # CRM accumulation: B requests x n items
    for B, n in [(200, 60), (2000, 600), (8000, 1024)]:
        H = (rng.random((B, n)) < 0.03).astype(np.float32)
        t_ref, want = _time(lambda h: np.asarray(ref.crm_ref(jnp.array(h))), H)
        t_k, got = _time(lambda h: ops.crm_matmul(jnp.array(h)), H)
        ok = bool(np.allclose(got, want))
        mxu_tiles = (-(-n // 128)) ** 2 * (-(-B // 128))
        rows.append((f"kernel/crm_update/B{B}_n{n}", int(t_k * 1e6),
                     f"allclose={ok};oracle_us={int(t_ref*1e6)};mxu_tiles={mxu_tiles}"))
        payload[f"crm_B{B}_n{n}"] = {"ok": ok, "kernel_s": t_k, "oracle_s": t_ref}

    # clique density
    for k, n in [(60, 60), (200, 512)]:
        M = (rng.random((k, n)) < 0.08).astype(np.float32)
        A = (rng.random((n, n)) < 0.2).astype(np.float32)
        t_ref, want = _time(lambda m, a: np.asarray(
            ref.clique_pair_edges_ref(jnp.array(m), jnp.array(a))), M, A)
        t_k, got = _time(lambda m, a: ops.pair_edges(jnp.array(m), jnp.array(a)), M, A)
        ok = bool(np.allclose(got, want))
        rows.append((f"kernel/clique_density/k{k}_n{n}", int(t_k * 1e6),
                     f"allclose={ok};oracle_us={int(t_ref*1e6)}"))
        payload[f"density_k{k}_n{n}"] = {"ok": ok}

    # packed lookup: one DMA descriptor per clique (an unpacked gather
    # would issue omega of them)
    omega, d, R, C = 5, 256, 64, 128
    table = rng.normal(size=(C, omega, d)).astype(np.float32)
    cids = rng.integers(0, C, R).astype(np.int32)
    t_p, got_p = _time(lambda: np.asarray(
        packed_lookup(jnp.array(table), jnp.array(cids))))
    ok = bool(np.array_equal(got_p, table[cids]))
    rows.append(("kernel/packed_lookup", int(t_p * 1e6),
                 f"equal={ok};dma_descriptors={R};bytes_per_dma={omega*d*4}"))
    payload["packed_lookup"] = {"ok": ok, "packed_descr": R}
    save_json("kernel_bench", payload)
    emit(rows)
    return rows


if __name__ == "__main__":
    main()
