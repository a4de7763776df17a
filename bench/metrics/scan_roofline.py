"""The queries' share in percent of the HBM roofline: the least bytes the
completed queries had to read (each referenced column, every row, once)
at the chip's published HBM bandwidth, over the device's busy time."""

from bench.peaks import peaks


def read(run):
    if run.trace is None or run.trace.busy_s <= 0 or not run.least_bytes:
        return None
    bw = peaks(run.device_kind)["hbm_bytes_per_s"]
    return run.least_bytes / bw / run.trace.busy_s * 100.0
