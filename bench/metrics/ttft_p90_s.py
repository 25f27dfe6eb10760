"""90th percentile, over every request of the window, of the time from
its submission (its call's start) to its first token."""

import numpy as np


def read(ctx):
    return float(np.percentile([t for c in ctx.calls for t in c.ttft_s()],
                               90))
