"""99th percentile of the time an operation waited in the router's queue:
its dispatch time minus its due time, over every operation answered."""
import numpy as np


def read(run):
    if run.queue_wait_s is None or len(run.queue_wait_s) == 0:
        return None
    return float(np.percentile(run.queue_wait_s, 99)) * 1e3
