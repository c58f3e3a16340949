"""Host time of the program's ``scatter_retire`` spans per 1000 valid
rows dispatched in the traced window, in us: scattering answers to their
requests, resolving futures, and the per-tenant stage sums and
``record_batch`` (``stats``). Read from the span ring (``serve``
category, the spans ``host_busy_share`` unites), where a stage's span
holds the spans nested in it."""
from bench.lib.hostsplit import ring_us_per_krow


def read(ctx):
    return ring_us_per_krow(ctx, "scatter_retire")
