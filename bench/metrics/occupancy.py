"""Valid rows over padded rows of the batches dispatched in the window,
in % (``ServeStats`` totals)."""


def read(ctx):
    s = ctx["serve"]
    if not s["padded_rows"]:
        return None
    return 100.0 * s["valid_rows"] / s["padded_rows"]
