"""The harness's parts on the CPU at a tiny scale: traffic generation,
metric arithmetic, the plain references and their control, the peaks
table, and finding a configuration, a mix and a metric by name."""
import json
import shutil

import numpy as np
import pytest

from bench import generator, harness, peaks, reference
from bench.tracereduce import Summary

TINY = {"scale_factor": 0.001}
CELLS = ["udf_queries.power", "udf_calls.open", "udf_calls.serial"]


def data_and_mods(workload, seed=3):
    c = harness.cell(workload)
    c.config.update(TINY)
    data = harness.generate(c.config, np.random.SeedSequence(seed))
    mods = {n: harness.load_named("statements", n)
            for n in c.config["statements"]}
    return c, data, mods


def test_unknown_device_kind_raises():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v99")


@pytest.mark.parametrize("seed", [1, 2**31 + 5])
def test_tpch_follows_the_specification(seed):
    """dbgen's rules: sparse order keys, no orders for customers whose key
    is a multiple of 3, 1 to 7 lines an order stored together, each line's
    supplier one of its part's, prices, flags, statuses and totals derived
    from the lines; the same row counts for every seed."""
    from bench.datasets import tpch

    _, data, _ = data_and_mods("udf_queries.power", seed)
    t = data.tables
    li, o, ps = t["lineitem"], t["orders"], t["partsupp"]
    n = tpch.rows(TINY["scale_factor"])
    assert {k: data.rows(k) for k in t} == {k: n[k] for k in t}
    assert {k: len(v) for k, v in t.items()} == {
        "region": 3, "nation": 4, "supplier": 7, "customer": 8, "part": 9,
        "partsupp": 5, "orders": 9, "lineitem": 16}
    assert set(o["o_orderkey"] % 32) <= set(range(1, 9))
    assert not (o["o_custkey"] % 3 == 0).any()
    row = data.row("orders", "o_orderkey", li["l_orderkey"])
    assert (np.diff(row) >= 0).all()
    counts = np.bincount(row, minlength=len(o["o_orderkey"]))
    assert counts.min() == 1 and counts.max() == 7
    assert (li["l_linenumber"] <= counts[row]).all()
    pairs = set(zip(ps["ps_partkey"].tolist(), ps["ps_suppkey"].tolist()))
    assert set(zip(li["l_partkey"].tolist(),
                   li["l_suppkey"].tolist())) <= pairs
    np.testing.assert_allclose(
        li["l_extendedprice"],
        li["l_quantity"] * tpch.retail_price(li["l_partkey"]), rtol=1e-6)
    flag, status = data.words("lineitem", "l_returnflag"), \
        data.words("lineitem", "l_linestatus")
    received = li["l_receiptdate"] <= tpch.CURRENTDATE
    assert set(flag[received]) == {"R", "A"} and set(flag[~received]) == {"N"}
    assert ((status == "O") == (li["l_shipdate"] > tpch.CURRENTDATE)).all()
    is_open = np.bincount(row, weights=status == "O") / counts
    want = np.where(is_open == 1, "O", np.where(is_open == 0, "F", "P"))
    assert (data.words("orders", "o_orderstatus") == want).all()
    total = np.bincount(row, weights=li["l_extendedprice"].astype(float)
                        * (1 + li["l_tax"]) * (1 - li["l_discount"]))
    np.testing.assert_allclose(o["o_totalprice"], total, rtol=1e-5)


@pytest.mark.parametrize("seed", [1, 2**31 + 5])
def test_open_plan_same_work_every_seed(seed):
    c, data, mods = data_and_mods("udf_calls.open")
    mix = {**c.mix, "rate_per_s": 400}
    p = generator.plan(mix, mods, data, np.random.default_rng(seed), 5.0)
    assert len(p.requests) == 2000
    counts = {n: sum(r.stmt == n for r in p.requests) for n in mods}
    assert set(counts.values()) == {2000 // len(mods)}
    gaps = np.diff([0.0] + [r.due for r in p.requests])
    np.testing.assert_allclose(np.sort(gaps),
                               np.sort(generator.poisson_gaps(2000, 400)))
    assert p.requests[-1].due == pytest.approx(5.0, rel=0.01)
    assert len(p.checked) == c.mix["checked"]


def test_closed_plans():
    c, data, mods = data_and_mods("udf_queries.power")
    p = generator.plan(c.mix, mods, data, np.random.default_rng(0), 5.0)
    assert [r.stmt for r in p.requests] == c.config["statements"]
    assert p.checked is None and p.cyclic
    c, data, mods = data_and_mods("udf_calls.serial")
    p = generator.plan(c.mix, mods, data, np.random.default_rng(0), 5.0)
    assert len(p.requests) == c.mix["cycle"] and p.cyclic
    assert all(r.params for r in p.requests)


def fake_run(workload):
    run = harness.Run(harness.cell(workload), device_kind="TPU v5 lite")
    run.window_start = 100.0
    run.calls = [harness.Call("s", 100.0 + i, 100.0 + i + 0.01 * (i + 1),
                              ok=True) for i in range(20)]
    run.sched_stats = ({"batches": 10, "drained": 50},
                       {"batches": 15, "drained": 80})
    run.trace = Summary(window_s=20.0, busy_s=5.0, sort_s=2.0, op_s={},
                        idle_by_host={})
    run.least_bytes = int(819e9)   # one second at the v5e's HBM peak
    run.setup_s, run.prepare_s, run.warmup_s = 40.0, [0.5, 0.25], 12.0
    return run


def test_metric_arithmetic():
    run = fake_run("udf_calls.open")
    read = {m: harness.load_named("metrics", m).read(run) for m in (
        "setup_s", "udf_queries_per_s", "calls_per_s", "call_p50_ms.open",
        "call_p95_ms.open", "call_p95_ms.serial", "prepare_ms", "warmup_s",
        "wave_size.open", "host_ms_per_call.serial", "sort_share.queries",
        "scan_roofline", "idle_share.queries", "idle_share.open",
        "idle_share.serial")}
    last = 100.0 + 19 + 0.2
    assert read["setup_s"] == 40.0 and read["warmup_s"] == 12.0
    assert read["prepare_ms"] == pytest.approx(750.0)
    assert read["udf_queries_per_s"] == pytest.approx(20 / (last - 100.0))
    assert read["calls_per_s"] == read["udf_queries_per_s"]
    assert read["call_p50_ms.open"] == pytest.approx(105.0)
    assert read["call_p95_ms.open"] == pytest.approx(
        np.percentile([10.0 * (i + 1) for i in range(20)], 95))
    assert read["call_p95_ms.serial"] == read["call_p95_ms.open"]
    assert read["wave_size.open"] == pytest.approx(6.0)
    assert read["host_ms_per_call.serial"] == pytest.approx(15.0 / 20 * 1e3)
    assert read["sort_share.queries"] == pytest.approx(40.0)
    assert read["scan_roofline"] == pytest.approx(20.0)
    assert read["idle_share.queries"] == pytest.approx(75.0)
    assert read["idle_share.open"] == pytest.approx(75.0)
    assert read["idle_share.serial"] == pytest.approx(75.0)


def test_metrics_read_nothing_without_a_trace():
    run = fake_run("udf_calls.serial")
    run.trace, run.sched_stats = None, None
    for m in ("wave_size.open", "host_ms_per_call.serial",
              "sort_share.queries", "scan_roofline", "idle_share.serial"):
        assert harness.load_named("metrics", m).read(run) is None


@pytest.mark.parametrize("workload", CELLS)
def test_reference_agrees_with_itself_and_control_fails(workload):
    """Every statement's float64 reference, served as the engine serves
    it, compares clean; the bfloat16 control reads over the limits."""
    from bench import control

    c, data, mods = data_and_mods(workload)
    p = generator.plan(c.mix, mods, data, np.random.default_rng(0), 2.0)
    tally, cache = reference.Tally(), {}
    for i in (range(len(p.requests)) if p.checked is None
              else sorted(p.checked)[:64]):
        r = p.requests[i]
        want = mods[r.stmt].reference(data, r.params, reference.F64, cache)
        tally.add(reference.served(want), want)
    assert tally.compared and tally.gap == 0 and tally.mismatches == 0
    checks = control.readings(workload, 4, 2.0, config={"scale_factor": 0.01})
    assert not reference.within(checks)


def test_compare_catches_each_kind_of_fault():
    ref = reference.answer({"k": [1, 2, 3], "v": [1.0, 2.0, 3.0]},
                           {"v": [1.0, 2.0, 3.0]}, keys=("k",),
                           order=(("v", False),), limit=2)
    ok = reference.served(ref)
    assert list(ok.cols["k"]) == [3, 2]

    def tally(got):
        t = reference.Tally()
        t.add(got, ref)
        return t.gap, t.mismatches

    assert tally(ok) == (0.0, 0)
    bad = reference.served(ref)
    bad.cols["v"] = bad.cols["v"] * (1 + 1e-3)
    assert tally(bad)[0] == pytest.approx(1e-3)
    swapped = reference.served(ref)
    swapped.cols = {"k": np.array([2, 3]), "v": np.array([2.0, 3.0])}
    assert tally(swapped)[1] == 1          # out of order
    short = reference.served(ref)
    short.cols = {k: v[:1] for k, v in short.cols.items()}
    short.valid = {k: v[:1] for k, v in short.valid.items()}
    assert tally(short)[1] == 1            # a row missing
    wrong_row = reference.served(ref)
    wrong_row.cols["k"] = np.array([3, 1])
    assert tally(wrong_row)[0] >= 0.5      # row 1 kept, row 2 beats it


def test_config_mix_and_metric_found_by_name(tmp_path):
    """A new cell needs only new files and entries: a configuration, a
    mix and a metric dropped beside the others are run by name."""
    shutil.copytree(harness.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((tmp_path / "bench/configs/tpch_sf1_udf_calls.json")
                     .read_text())
    cfg.update(name="tpch_tiny_q1", statements=["q1_revenue"])
    (tmp_path / "bench/configs/tpch_tiny_q1.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/traffic/burst.json").write_text(json.dumps(
        {"loop": "open", "calls": "mix", "path": "scheduler",
         "scheduler": {"fuse": False, "interp_fallback": False},
         "warm_batches_up_to": 8, "rate_per_s": 50,
         "checked": 16}))
    (tmp_path / "bench/metrics/calls_seen.py").write_text(
        "def read(run):\n    return len(run.calls)\n")
    bench["configs"].append({"name": "tpch_tiny_q1", "source": "x",
                             "file": "bench/configs/tpch_tiny_q1.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "tiny.burst", "config": "tpch_tiny_q1",
                               "traffic": "burst", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "calls_seen", "unit": "calls",
                               "better": "higher", "source": "host_clock",
                               "layer": "scheduler", "moves": "call_p95_ms",
                               "workloads": ["tiny.burst"]})
    bench["end_to_end"][2]["workloads"].append("tiny.burst")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    c = harness.cell("tiny.burst", root=tmp_path)
    assert c.config["statements"] == ["q1_revenue"]
    assert c.mix["rate_per_s"] == 50
    assert [m["name"] for m in c.end_to_end] == ["setup_s",
                                                 "call_p50_ms.open"]
    assert [m["name"] for m in c.per_layer] == ["calls_seen"]
    metric = harness.load_named("metrics", "calls_seen", root=tmp_path)
    assert metric.read(fake_run("udf_calls.open")) == 20
