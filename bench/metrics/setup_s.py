"""Seconds from the start of the run's process to the opening of the
measured window: JAX start-up, data generation and load, prepare, and
warm-up (compilation too, where the compile cache is cold)."""


def read(run):
    return run.setup_s
