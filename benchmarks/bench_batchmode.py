"""Table 4 (columnstore / batch-mode execution): the same UDF query with
row-at-a-time iteration vs the sort-based set-oriented group-by vs the
fused relagg Pallas kernel (batch mode) — the TPU analogue of the paper's
row store vs columnstore comparison.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from benchmarks.common import emit, time_run
from repro.core import (
    FROID,
    HEKATON,
    Session,
    UdfBuilder,
    col,
    param,
    scan,
    sum_,
    udf,
)
from repro.data.tpch import generate_tpch


def table4_query():
    """The single-dictionary-key group-by the relagg kernel serves
    (``l_returnflag``: 3 groups), over the ``discount_price`` UDF."""
    return (
        scan("lineitem")
        .filter(col("l_quantity") > 10)
        .group_by(
            "l_returnflag",
            rev=sum_(udf("discount_price", col("l_extendedprice"),
                         col("l_discount"))),
        )
    )


def run(quick: bool = False, sf: float = 0.02):
    db = Session()
    generate_tpch(db, sf=sf)

    u = UdfBuilder("discount_price",
                   [("price", "float32"), ("disc", "float32")], "float32")
    u.return_(param("price") * (1.0 - param("disc")))
    db.create_function(u.build())

    q = table4_query()

    fn_sort = db.prepare(q, FROID)
    t_sort = time_run(fn_sort)
    emit("table4/froid_on_rowstore(sort-groupby)", t_sort * 1e6, "")

    batch_mode = dataclasses.replace(FROID, pallas_agg=True, compile_plan=False)

    def run_pallas():
        return db.execute(q, batch_mode).masked.mask

    # NB: pallas interpret-mode on CPU measures dispatch, not MXU speed —
    # the batch-mode win is structural (no sort; one fused pass); we also
    # report the sort cost it eliminates.
    t_pal = time_run(run_pallas, warmup=1, iters=1)
    emit("table4/froid_on_batchmode(relagg)", t_pal * 1e6,
         f"vs_sort={t_sort/t_pal:.2f}x (interpret-mode timing)")

    n = db.catalog["lineitem"].num_rows
    fn_off = db.prepare(q, HEKATON)
    t_off = time_run(fn_off, warmup=1, iters=1)
    emit("table4/froid_off_iterative", t_off * 1e6,
         f"rows={n} slowdown_vs_batch={t_off/t_sort:.1f}x")


if __name__ == "__main__":
    run()
