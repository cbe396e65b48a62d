"""Admission (``serving/slots.py`` queues): 90th percentile of the time
from a request's due time to its admission into a slot, over the
requests due in the window, from the engine's own admission times."""

import numpy as np


def read(ctx):
    waits = ctx.get("queue_waits")
    if not waits:
        return None
    return 1e3 * float(np.percentile(np.asarray(waits), 90))
