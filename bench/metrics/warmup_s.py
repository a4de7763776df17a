"""Seconds of the warm-up calls that run every shape the window uses once
(tracing, compile-cache loads or compiles, first executions); host
clock."""


def read(run):
    return run.warmup_s
