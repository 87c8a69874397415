"""Sweep entry point (``SweepEngine.run``): share of the window's grid
points that ran as device-CGM lanes, in %, from the engine's ``stats``
counters; the rest fell back to a host clique walk."""


def read(r):
    lanes, points = r.harness.get("cgm_lanes"), r.harness.get("points")
    if lanes is None or not points:
        return None
    return 100.0 * lanes / points
