"""Device-idle milliseconds inside each ``bench.call`` span, averaged over
the traced calls: dispatch, host-side batch handling and the host's
summary between device programs."""

import numpy as np


def read(view):
    return float(np.mean(view.idle_ns_per_call())) * 1e-6
