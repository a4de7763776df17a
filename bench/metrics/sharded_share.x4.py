"""Share in percent of the window's scheduler waves that ran on the whole
mesh: the change in the scheduler's ``sharded_waves`` counter over the
change in its ``batches``.  A scheduler without placement counters reads
nothing."""


def read(run):
    if run.sched_stats is None:
        return None
    s0, s1 = run.sched_stats
    waves = s1["batches"] - s0["batches"]
    if "sharded_waves" not in s1 or waves <= 0:
        return None
    return (s1["sharded_waves"] - s0["sharded_waves"]) / waves * 100.0
