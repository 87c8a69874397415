"""The program's own ``akpc.*`` spans beside the harness's ``bench.*``
spans in one real CPU profiler trace: the harness's trace reduction reads
its own spans only, so every accepted metric and gap label reads the same
inputs as before the program had spans, and the program's spans sit
inside the harness's window on the same clock.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q tests/bench
"""
import jax

import program
import tracing
from repro import obs
from repro.traces import SynthConfig, synth_trace


def test_harness_reads_its_spans_only(tmp_path):
    tr = synth_trace(SynthConfig(
        kind="netflix", n_items=60, n_servers=12, n_requests=600,
        t_max=5.0, bundle_cover=1.0, bundle_zipf=0.7, seed=5))
    eng = program.LiveServingEngine(
        program.policy({"policy": {"name": "akpc", "t_cg": 0.73,
                                   "top_frac": 1.0}, "costs": {}}),
        tr.n, tr.m, chunk_size=128, ring=1)
    tracing.start(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.submit"):
                for lo in range(0, 600, 100):
                    eng.submit(tr.items[lo:lo + 100],
                               tr.servers[lo:lo + 100],
                               tr.times[lo:lo + 100])
            with jax.profiler.TraceAnnotation("bench.drain"):
                eng.drain()
    finally:
        jax.profiler.stop_trace()

    devices, spans = tracing.load(str(tmp_path))
    assert devices == []                  # no TPU plane on the CPU
    assert sorted(n for n, _, _ in spans) == [
        "bench.drain", "bench.submit", "bench.window"]
    (w0, wd), = [(s, d) for n, s, d in spans if n == "bench.window"]

    prog = obs.read(str(tmp_path))
    names = {n for n, *_ in prog}
    assert {"akpc.live.pack", "akpc.live.put", "akpc.live.launch",
            "akpc.live.ring_wait", "akpc.live.sync"} <= names
    assert all(w0 <= s and s + d <= w0 + wd for _, s, d, _ in prog)
    assert sum(1 for n, *_ in prog if n == "akpc.live.launch") \
        == eng.stats["chunks"] == 5
