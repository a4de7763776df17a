"""Host milliseconds per call: the traced window less the device's busy
time, over the calls completed in it."""


def read(run):
    done = sum(c.ok for c in run.calls)
    if run.trace is None or not done:
        return None
    return (run.trace.window_s - run.trace.busy_s) / done * 1e3
