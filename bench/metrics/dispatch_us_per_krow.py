"""Host time of the program's ``dispatch`` spans per 1000 valid rows
dispatched in the traced window, in us: the grouped program's host side:
the arena's tile-cache lookup and, on a miss, the per-tile weight gather
(``tiles``), then the row batch's transfer and the fused program's
launch (``launch``). Read from the span ring (``serve`` category, the
spans ``host_busy_share`` unites), where a stage's span holds the spans
nested in it."""
from bench.lib.hostsplit import ring_us_per_krow


def read(ctx):
    return ring_us_per_krow(ctx, "dispatch")
