"""95th percentile of the window's round times, in ms. A round is timed
from its input placement to the read-back of its losses and weights."""
import numpy as np


def read(ctx):
    if not ctx.round_s:
        return None
    return float(np.percentile(np.asarray(ctx.round_s) * 1e3, 95))
