"""95th percentile (nearest rank) of the wall time of every query of the
window, all statements together. Nothing below 40 queries: under that a
95th percentile has fewer than two samples beyond it."""
import math


def read(run):
    walls = sorted(q["wall_s"] for q in run["window"]["queries"] if q["ok"])
    if len(walls) < 40:
        return None
    return walls[math.ceil(0.95 * len(walls)) - 1]
