"""Share in percent of the rows the window's waves ran that were padding:
the change in the scheduler's ``pad_calls`` counter over that change plus
the change in its ``drained`` calls.  A scheduler without placement
counters reads nothing."""


def read(run):
    if run.sched_stats is None:
        return None
    s0, s1 = run.sched_stats
    if "pad_calls" not in s1:
        return None
    pad = s1["pad_calls"] - s0["pad_calls"]
    rows = s1["drained"] - s0["drained"] + pad
    return pad / rows * 100.0 if rows > 0 else None
