"""Seconds the process spent tracing, lowering and compiling programs
before the window opened (a load from the persistent compilation cache
counts as a compile), overlaps counted once: the engine's
``jax.monitoring`` listener in ``repro.telemetry``."""


def read(run):
    try:
        from repro import telemetry
    except ImportError:   # a program without the listener
        return None
    return telemetry.busy_seconds(telemetry.COMPILE, until=run.window_start)
