"""Median response time of every query completed inside the window, each
timed on the host clock from its submission to its completion callback."""

import numpy as np


def read(rec):
    lat = rec["latencies"]
    return float(np.percentile(lat, 50)) if lat else None
