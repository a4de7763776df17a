"""Milliseconds spent in ``Session.prepare`` (bind, Froid inlining,
optimize) for the cell's statements, summed; host clock."""


def read(run):
    return sum(run.prepare_s) * 1e3
