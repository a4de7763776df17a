"""Milliseconds a call waited in the scheduler, from its ``submit`` to
the moment its wave held the drain lock: the change in the scheduler's
``queue_wait_s`` over the change in its ``drained`` counter across the
window."""


def read(run):
    if run.sched_stats is None:
        return None
    s0, s1 = run.sched_stats
    calls = s1["drained"] - s0["drained"]
    if "queue_wait_s" not in s1 or calls <= 0:
        return None
    return (s1["queue_wait_s"] - s0["queue_wait_s"]) / calls * 1e3
