"""99th percentile of the client's time over every read request (a
``get_batch`` call) started in the window, from its call to its return."""
import numpy as np

from chipbench.harness import READ


def read(run):
    lat = run.latencies_ms([READ])
    return float(np.percentile(lat, 99)) if len(lat) else None
