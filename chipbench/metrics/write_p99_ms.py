"""99th percentile of the client's time over every write request (a
``put_batch`` call) started in the window, from its call to its return."""
import numpy as np

from chipbench.harness import UPDATE


def read(run):
    lat = run.latencies_ms([UPDATE])
    return float(np.percentile(lat, 99)) if len(lat) else None
