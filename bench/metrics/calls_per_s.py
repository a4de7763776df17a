"""Calls answered per second: every call completed, over the time from
the window's opening to the last completion."""

from bench.harness import completed_per_s as read  # noqa: F401
