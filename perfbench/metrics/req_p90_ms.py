"""90th percentile (numpy's linear interpolation) of latency from scheduled
send to the router tick that hands back the last token, over every request
due in the window; a failed request counts at the cap."""
import numpy as np


def read(r):
    return float(np.percentile(r.window.latency_s(), 90)) * 1e3
