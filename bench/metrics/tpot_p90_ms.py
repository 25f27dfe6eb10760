"""90th percentile, over every request of the window, of
``(finish - first token) / (tokens - 1)``, in milliseconds."""

import numpy as np


def read(ctx):
    return 1e3 * float(np.percentile(
        [t for c in ctx.calls for t in c.tpot_s()], 90))
