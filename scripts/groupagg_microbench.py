"""Grouped-sum lowerings at TPC-H SF 1's lineitem size, on the device.

Times three ways to sum 5,999,995 float32 rows into G groups, where a
row's group id is in [0, G] and G is the overflow slot (rows filtered out
or outside the key range):

* ``where_gn``: one masked reduction per group, the (G, n) comparison of
  ``gid`` against ``arange(G)`` selected and summed along the rows;
* ``where_ng``: the same with the (n, G) layout, reduced over axis 0;
* ``segment``: ``jax.ops.segment_sum`` over G + 1 segments, the slice [:G].

Each is jitted with one and with eight value columns (Q1 reduces eight
float32 inputs per group), warmed up, then timed over ``--reps`` calls
that end in ``block_until_ready``; the median is printed.  Each result is
also compared with a float64 sum on the host.  One JSON line per case on
stdout, and all of them in the file ``--out`` names, if any.

    python scripts/groupagg_microbench.py [--rows 5999995] [--reps 20] \
        [--out results.json]

What ``executor.SMALL_GROUP_DOMAIN`` was set from: the masked reduction's
cost grows with G, ``segment_sum``'s does not, and the limit is the
largest G at which the reduction stays a few per cent of the scatter.
"""
from __future__ import annotations

import argparse
import json
import statistics
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np


def where_gn(gid, xs, G):
    hit = jnp.arange(G, dtype=jnp.int32)[:, None] == gid[None, :]
    return [jnp.sum(jnp.where(hit, x[None, :], 0.0), axis=1) for x in xs]


def where_ng(gid, xs, G):
    hit = gid[:, None] == jnp.arange(G, dtype=jnp.int32)[None, :]
    return [jnp.sum(jnp.where(hit, x[:, None], 0.0), axis=0) for x in xs]


def segment(gid, xs, G):
    return [jax.ops.segment_sum(x, gid, num_segments=G + 1)[:G] for x in xs]


LOWERINGS = {"where_gn": where_gn, "where_ng": where_ng, "segment": segment}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=5_999_995)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--groups", default="6,25,64,256,1024,4096")
    ap.add_argument("--out", type=Path, default=None)
    args = ap.parse_args()
    dev = jax.devices()[0]
    rng = np.random.default_rng(18)
    n = args.rows
    xs_host = [rng.uniform(0.0, 1e5, n).astype(np.float32) for _ in range(8)]
    xs = [jnp.asarray(x) for x in xs_host]
    out = []
    for G in (int(g) for g in args.groups.split(",")):
        g_host = rng.integers(0, G, n).astype(np.int32)
        g_host[rng.uniform(size=n) < 0.1] = G      # a tenth filtered out
        gid = jnp.asarray(g_host)
        want = [np.bincount(g_host, weights=x.astype(np.float64),
                            minlength=G + 1)[:G] for x in xs_host]
        for k in (1, 8):
            for name, fn in LOWERINGS.items():
                f = jax.jit(lambda g, *v, fn=fn, G=G: fn(g, list(v), G))
                t0 = time.perf_counter()
                try:
                    got = jax.block_until_ready(f(gid, *xs[:k]))
                except Exception as e:     # e.g. a lowering past HBM
                    row = {"G": G, "inputs": k, "lowering": name,
                           "error": f"{type(e).__name__}: {e}"[:300]}
                    out.append(row)
                    print(json.dumps(row), flush=True)
                    continue
                compile_s = time.perf_counter() - t0
                times = []
                for _ in range(args.reps):
                    t0 = time.perf_counter()
                    jax.block_until_ready(f(gid, *xs[:k]))
                    times.append(time.perf_counter() - t0)
                gap = max(float(np.max(np.abs(np.asarray(r, np.float64) - w)
                                       / np.maximum(np.abs(w), 1.0)))
                          for r, w in zip(got, want))
                row = {"G": G, "inputs": k, "lowering": name,
                       "median_ms": statistics.median(times) * 1e3,
                       "min_ms": min(times) * 1e3, "compile_s": compile_s,
                       "rel_gap_vs_f64": gap, "rows": n,
                       "device": dev.device_kind, "platform": dev.platform}
                out.append(row)
                print(json.dumps(row), flush=True)
    if args.out is not None:
        args.out.write_text(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
