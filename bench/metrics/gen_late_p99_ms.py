"""99th percentile of how late the load generator sent its requests."""

import numpy as np


def read(ctx):
    c = ctx.get("client")
    if c is None:
        return None
    late = c["late_s"][np.isfinite(c["late_s"])]
    return float(np.percentile(late, 99)) * 1e3 if late.size else None
