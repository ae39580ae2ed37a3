"""Median latency, client send to reply, over every query of the window."""

import numpy as np


def read(ctx):
    lat = [(r.end - r.start) * 1e3 for r in ctx.records if r.ok]
    return float(np.percentile(lat, 50)) if lat else None
