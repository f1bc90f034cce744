"""95th percentile of the window's frame latencies, round start to output
on the host, over every frame completed in the window."""

import numpy as np


def read(rec):
    lat = rec.get("frame_latencies_s")
    return float(np.percentile(lat, 95)) * 1e3 if lat else None
