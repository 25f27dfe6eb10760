"""Mean decode ticks a request of the window waited for a slot
(``admit_tick - submit_tick``, from each call's ``ServeReport``)."""

import numpy as np


def read(ctx):
    return float(np.mean([t.queue_wait_ticks for c in ctx.calls
                          for t in c.report.requests]))
