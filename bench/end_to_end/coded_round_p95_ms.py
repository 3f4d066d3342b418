"""95th percentile of the wall time of every coded round in the window, in
milliseconds; each round ends when its decoded answer is on the host."""

import numpy as np


def value(window: dict) -> float:
    times = np.asarray([t1 - t0 for t0, t1, _ in window["calls"]])
    return float(np.percentile(times, 95.0) * 1e3)
