"""Share in percent of the device's busy time spent in HLO sort
operations, from the trace."""


def read(run):
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    return run.trace.sort_s / run.trace.busy_s * 100.0
