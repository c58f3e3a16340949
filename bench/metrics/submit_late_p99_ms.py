"""How late the load generator submitted, against the due times: the
99th percentile over the window's requests, in ms. Open loop only."""
import numpy as np


def read(ctx):
    late = ctx["late_s"]
    if late is None:
        return None
    late = late[~np.isnan(late)]
    return float(np.percentile(late, 99)) * 1e3 if len(late) else None
