"""The benchmark's modules (``bench/``) import each other by their flat
names (``run``, ``program``, ``gen``...): put ``bench/`` first on the
path, ahead of the repository root's own ``run.py``."""
import os
import sys

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "bench")
sys.path.insert(0, BENCH)
for _name in ("run", "program", "compare", "gen", "tracing", "control"):
    _mod = sys.modules.get(_name)
    if _mod is not None and not str(getattr(_mod, "__file__", "")
                                    ).startswith(BENCH):
        del sys.modules[_name]
