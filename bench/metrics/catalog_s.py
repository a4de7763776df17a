"""Seconds the engine spent loading the catalog before the window opened
(``Session.create_table``: each table and its column statistics), from
the catalog events ``repro.telemetry`` records."""


def read(run):
    try:
        from repro import telemetry
    except ImportError:   # a program without the listener
        return None
    return telemetry.busy_seconds(("catalog",), until=run.window_start)
