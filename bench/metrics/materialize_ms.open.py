"""Milliseconds the engine took to materialize one call's answer out of
its wave's device outputs: the ``froid.materialize`` spans that start in
the traced window, over their number."""

from bench import enginetrace
from bench.harness import TRACE_DIR


def read(run):
    if run.trace is None:
        return None
    return enginetrace.load(str(TRACE_DIR)).mean_ms(
        ["froid.materialize"], per="froid.materialize")
