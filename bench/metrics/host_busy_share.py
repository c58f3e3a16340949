"""Share of the window in which the program's host spans (``prepare``,
``dispatch``, ``device_block``, ``scatter_retire``) ran, in %: their
union over the window. Where the span ring dropped spans, over the part
of the window that the kept spans cover."""
import numpy as np


def read(ctx):
    spans = ctx["spans"]
    if not spans:
        return None
    iv = np.asarray(sorted((s, e) for _, s, e in spans))
    t0 = ctx["t0"] if not ctx["spans_dropped"] else iv[0, 0]
    t1 = ctx["t1"]
    busy, end = 0.0, t0
    for s, e in iv:
        s = max(s, end)
        if e > s:
            busy += e - s
            end = e
    return 100.0 * busy / (t1 - t0) if t1 > t0 else None
