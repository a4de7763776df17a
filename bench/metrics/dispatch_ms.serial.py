"""Host milliseconds per device program, from the engine's executable
lookup to the return of its dispatch call: the ``froid.args`` and
``froid.dispatch`` spans that start in the traced window, over the
number of ``froid.execute`` spans there."""

from bench import enginetrace
from bench.harness import TRACE_DIR


def read(run):
    if run.trace is None:
        return None
    return enginetrace.load(str(TRACE_DIR)).mean_ms(
        ["froid.args", "froid.dispatch"], per="froid.execute")
