"""The 99th percentile of the latency of every request answered in the
window, from when it was due to when its future resolved, in ms. Open
loop only. A host that stands still for a tenth of a second backs the
queue up for some hundreds of ms, so this tail swings from run to run
with whether such a stall fell in the window."""
import numpy as np


def read(ctx):
    lat = ctx["latency_s"]
    if lat is None:
        return None
    lat = lat[~np.isnan(lat)]
    return float(np.percentile(lat, 99)) * 1e3 if len(lat) else None
