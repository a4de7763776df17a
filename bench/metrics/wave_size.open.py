"""Calls per scheduler wave over the window: the change in the
scheduler's ``drained`` counter over the change in its ``batches``."""


def read(run):
    if run.sched_stats is None:
        return None
    s0, s1 = run.sched_stats
    waves = s1["batches"] - s0["batches"]
    if waves <= 0:
        return None
    return (s1["drained"] - s0["drained"]) / waves
