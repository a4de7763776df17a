"""Queries answered per second: every query completed, over the time from
the window's opening to the last completion (the last pass begun inside
the window runs to its end)."""

from bench.harness import completed_per_s as read  # noqa: F401
