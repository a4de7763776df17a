"""Share in percent of the device's busy time spent in ops of the
engine's joins (the ``froid.join`` operator scope; a ``while`` and its
body counted once), from the trace."""

from bench import enginetrace
from bench.harness import TRACE_DIR


def read(run):
    if run.trace is None:
        return None
    return enginetrace.load(str(TRACE_DIR)).share("froid.join")
