"""Engine admission: 95th percentile of the time a request waited from
when it was due to when the front end handed it to ``admit``, over every
request of the window (one never admitted counts until the run gave up)."""
import numpy as np

UNIT = "ms"


def read(ctx):
    waits = [(r.admit_start if r.admit_start is not None else ctx.give_up)
             - r.due for r in ctx.records]
    return 1e3 * float(np.percentile(waits, 95)) if waits else None
