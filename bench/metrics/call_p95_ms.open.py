"""95th-percentile latency in milliseconds of all calls of the window, from when
each was due to when its whole answer was on the host; a failed call
counts with the time it took to fail."""

import numpy as np

from bench.harness import latencies_ms


def read(run):
    return float(np.percentile(latencies_ms(run), 95)) if run.calls else None
