"""99th percentile, over every call of the window, of the time between
consecutive decode ticks' token fetches (``ServeReport.tick_end_s``):
what a live request waits between two of its tokens, admissions on the
tick included.  None where the reports carry no such counter."""

import numpy as np


def read(ctx):
    gaps = []
    for c in ctx.calls:
        ends = getattr(c.report, "tick_end_s", None)
        if ends is not None and len(ends) > 1:
            gaps.extend(np.diff(ends))
    return 1e3 * float(np.percentile(gaps, 99)) if gaps else None
