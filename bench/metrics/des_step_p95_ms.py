"""95th percentile of the host time of one DES step to the next frame
tick (``repro.des.run``; the drain to the horizon is left out)."""

import numpy as np

from bench import spans

NAME = "repro.des.run"


def read(record):
    s = spans.load(record)
    if not spans.count(s, NAME):
        return None
    return 1e3 * float(np.percentile(s["durations"][NAME], 95))
