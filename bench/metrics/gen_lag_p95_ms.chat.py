"""95th percentile of how late the open-loop generator sent requests
after they were due (submit returned minus due time), in ms."""
import numpy as np


def read(run):
    if not run.gen_lags_s:
        return None
    return 1e3 * float(np.percentile(run.gen_lags_s, 95))
