"""95th percentile of the host wall time of every call in the window, in
milliseconds (linear interpolation between order statistics)."""
import numpy as np


def read(ctx):
    if not ctx.calls:
        return None
    return float(np.percentile([c.wall for c in ctx.calls], 95)) * 1e3
