"""The placement layer's metric readers (``sharded_share.x4``,
``pad_share.x4``) on synthesized scheduler counters."""
import pytest

from bench import harness


def run_with(s0, s1):
    run = harness.Run(harness.cell("udf_calls.open.x4"))
    run.sched_stats = (s0, s1)
    return run


def read(metric, run):
    return harness.load_named("metrics", metric).read(run)


def test_metric_arithmetic():
    run = run_with(
        {"batches": 10, "drained": 12, "sharded_waves": 10,
         "sharded_calls": 12, "pad_calls": 30},
        {"batches": 110, "drained": 132, "sharded_waves": 100,
         "sharded_calls": 118, "pad_calls": 310})
    # 90 of 100 waves on the mesh; 280 padding rows beside 120 calls
    assert read("sharded_share.x4", run) == pytest.approx(90.0)
    assert read("pad_share.x4", run) == pytest.approx(280 / 400 * 100)


def test_every_wave_sharded_and_none_padded():
    run = run_with({"batches": 0, "drained": 0, "sharded_waves": 0,
                    "pad_calls": 0},
                   {"batches": 5, "drained": 20, "sharded_waves": 5,
                    "pad_calls": 0})
    assert read("sharded_share.x4", run) == 100.0
    assert read("pad_share.x4", run) == 0.0


def test_nothing_to_read():
    """No scheduler, a scheduler without placement counters (as before
    they existed), or an empty window: the readers return None."""
    empty = harness.Run(harness.cell("udf_calls.open.x4"))
    old = run_with({"batches": 1, "drained": 1}, {"batches": 4, "drained": 9})
    idle = run_with({"batches": 3, "drained": 3, "sharded_waves": 3,
                     "pad_calls": 0},
                    {"batches": 3, "drained": 3, "sharded_waves": 3,
                     "pad_calls": 0})
    for metric in ("sharded_share.x4", "pad_share.x4"):
        for run in (empty, old, idle):
            assert read(metric, run) is None
