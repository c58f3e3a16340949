"""Device time of the grouped program (the ``jit_fused_body`` and
``jit_gather_tiles`` modules in the profiler trace) per 1000 valid
rows dispatched in the traced window, in us."""
from bench.lib.work import program_s


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not ctx["serve"]["valid_rows"] or not program_s(tr):
        return None
    return program_s(tr) * 1e6 / (ctx["serve"]["valid_rows"] / 1e3)
