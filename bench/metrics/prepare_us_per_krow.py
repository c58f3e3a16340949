"""Host time of the program's ``prepare`` spans per 1000 valid rows
dispatched in the traced window, in us: coalescing the queued requests
into a padded batch. Read from the span ring (``serve`` category, the
spans ``host_busy_share`` unites), where a stage's span holds the spans
nested in it."""
from bench.lib.hostsplit import ring_us_per_krow


def read(ctx):
    return ring_us_per_krow(ctx, "prepare")
