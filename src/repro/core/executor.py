"""Set-oriented, vectorized plan executor.

Design (TPU adaptation of the paper's set-oriented plans, DESIGN.md §2):

* **Selection vectors, not compaction** — a plan value is a
  :class:`MaskedTable` (full-width columns + bool row mask).  Filters AND
  into the mask; no operator has a data-dependent output shape, so whole
  plans trace under ``jax.jit`` / ``vmap`` (which is how correlated Apply
  falls back to vectorized evaluation instead of a row loop).
* **Joins** — each probe row takes the lowest-numbered live build row
  with its key.  A join the optimizer annotated with a dense key range
  (``Join.dense_range``: one integer build key over a stats range at most
  4× its distinct count, on a build no parameter or outer row reaches)
  addresses the build directly: a scatter-min of build row numbers into a
  slot table over the range, one gather per probe row.  Every other join
  (composite, float, sparse or computed keys, grouped or parameter-bound
  builds) sorts the build side and ``searchsorted``s the probe keys.  No
  hash tables.
* **Group-by** — sort-based segmenting + ``jax.ops.segment_sum`` with a
  *static* group capacity (default: the row count), or the fused Pallas
  ``relagg`` kernel for the single-pass filter+project+aggregate hot path.
* **CSE for free** — node results are memoized per execution, which is the
  relational version of common-subexpression elimination (paper §6).
* **Named operators** — each node runs under ``jax.named_scope`` of its
  operator family (:data:`SCOPES`), so the device ops it lowers to carry
  ``froid.<family>`` in their op-name metadata; a child's scope nests in
  its parent's, and the innermost ``froid.*`` component names the family
  an op belongs to.  The scopes cost nothing at run time.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core import relalg as R
from repro.core import scalar as S
from repro.tables.table import Column, Table

_F32_MAX = jnp.finfo(jnp.float32).max
_I32_MAX = jnp.iinfo(jnp.int32).max


@dataclasses.dataclass
class MaskedTable:
    table: Table
    mask: jnp.ndarray  # bool (n,)

    @property
    def num_rows(self) -> int:
        return int(self.mask.shape[0])

    def env(self) -> dict[str, S.Value]:
        return {
            n: S.Value(c.data, c.valid, c.dictionary)
            for n, c in self.table.columns.items()
        }

    def compact(self) -> Table:
        """Host-side materialization of selected rows (not jit-safe; used
        only at result-delivery time)."""
        import numpy as np

        idx = np.nonzero(np.asarray(self.mask))[0]
        return self.table.gather(jnp.asarray(idx))


def _value_to_column(v: S.Value, n: int) -> Column:
    b = v.broadcast(n)
    return Column(b.data, b.valid, b.dictionary)


def _scalar_value(v: S.Value) -> S.Value:
    """Coerce a Value to scalar (shape ``()``) leaves — loop-carry state
    is rank-0 regardless of how broadcasting shaped the evaluation."""
    d = jnp.asarray(v.data)
    if d.ndim > 0:
        d = d.reshape(-1)[0]
    val = jnp.asarray(v.validity())
    if val.ndim > 0:
        val = val.reshape(-1)[0]
    return S.Value(d, val, v.dictionary)


def _sort_key_for(col: Column, mask: jnp.ndarray) -> jnp.ndarray:
    """Key array with masked/NULL rows pushed to the end (+inf sentinel)."""
    data = col.data
    ok = mask & col.validity()
    if jnp.issubdtype(data.dtype, jnp.floating):
        return jnp.where(ok, data, _F32_MAX)
    return jnp.where(ok, data.astype(jnp.int32), _I32_MAX)


def _union_dense_rank(left: "MaskedTable", right: "MaskedTable", on):
    """Composite-key equality via one synthetic int32 key per side.

    Lexicographically sorts the *union* of both sides' key tuples
    (stable argsort composition, least-significant key first), marks run
    boundaries, and cumsums them into dense group ids — equal tuples get
    equal ids regardless of side, so the ordinary single-key sort-merge
    applies.  Rows with any masked/NULL key component map to the int32
    sentinel and never match (matching single-key NULL semantics)."""
    nl = left.num_rows
    n = nl + right.num_rows
    parts = []
    lvalid = left.mask
    rvalid = right.mask
    for lc, rc in on:
        lk = left.table.columns[lc]
        rk = right.table.columns[rc]
        lvalid = lvalid & lk.validity()
        rvalid = rvalid & rk.validity()
        parts.append(jnp.concatenate([
            _sort_key_for(lk, left.mask), _sort_key_for(rk, right.mask),
        ]))
    order = jnp.arange(n)
    for u in reversed(parts):
        order = jnp.take(order, jnp.argsort(jnp.take(u, order), stable=True))
    newgrp = jnp.zeros((n,), bool).at[0].set(True)
    for u in parts:
        su = jnp.take(u, order)
        newgrp = newgrp | (su != jnp.roll(su, 1)).at[0].set(True)
    gid = jnp.zeros((n,), jnp.int32).at[order].set(
        jnp.cumsum(newgrp.astype(jnp.int32)) - 1
    )
    lkeys = jnp.where(lvalid, gid[:nl], _I32_MAX)
    rkeys = jnp.where(rvalid, gid[nl:], _I32_MAX)
    return lkeys, rkeys


#: the operator family each plan node's device ops are scoped under
SCOPES = {
    R.Scan: "froid.scan", R.ConstantScan: "froid.scan",
    R.Filter: "froid.filter",
    R.Project: "froid.project", R.Compute: "froid.project",
    R.Join: "froid.join", R.Apply: "froid.apply",
    R.GroupAgg: "froid.groupagg", R.Sort: "froid.sort",
    R.LoopScan: "froid.loopscan",
}


class Executor:
    """Evaluates relational plans over a catalog of named Tables."""

    def __init__(
        self,
        catalog: dict[str, Table],
        udf_column_evaluator: Callable | None = None,
        use_pallas_agg: bool = False,
    ):
        self.catalog = catalog
        # froid-OFF hook: computes a whole column by iterating the UDF per
        # row (repro.core.interpreter wires this in)
        self.udf_column_evaluator = udf_column_evaluator
        self.use_pallas_agg = use_pallas_agg
        self._stats = {"bytes_scanned": 0, "rows_scanned": 0}

    @property
    def stats(self) -> dict:
        """Logical-read counters (copy; accumulates across executions)."""
        return dict(self._stats)

    def _sub_executor(self) -> "Executor":
        """Executor used for nested plan evaluation (correlated applies,
        scalar subqueries, EXISTS).  Subclasses override to propagate extra
        state — the fused SharedScanExecutor carries its shared-result
        pools into subquery bodies this way."""
        return Executor(self.catalog, self.udf_column_evaluator,
                        self.use_pallas_agg)

    # -- public API --------------------------------------------------------
    def execute(self, plan: R.RelNode, params=None, outer=None, vars=None) -> MaskedTable:
        ctx = S.EvalContext(
            executor=self, params=params or {}, outer=outer or {}, vars=vars or {}
        )
        memo: dict[int, MaskedTable] = {}
        return self._exec(plan, ctx, memo)

    # -- node dispatch -----------------------------------------------------
    def _exec(self, node: R.RelNode, ctx, memo) -> MaskedTable:
        key = node.node_id
        if key in memo:
            return memo[key]
        with jax.named_scope(SCOPES.get(type(node), "froid")):
            out = self._exec_node(node, ctx, memo)
        memo[key] = out
        return out

    def _exec_node(self, node: R.RelNode, ctx, memo) -> MaskedTable:
        if isinstance(node, R.Scan):
            t = self.catalog[node.table]
            self._stats["bytes_scanned"] += t.nbytes()
            self._stats["rows_scanned"] += t.num_rows
            n = t.num_rows
            return MaskedTable(t, jnp.ones((n,), bool))

        if isinstance(node, R.ConstantScan):
            return MaskedTable(Table({}), jnp.ones((1,), bool))

        if isinstance(node, R.Compute):
            child = self._exec(node.child, ctx, memo)
            n = child.num_rows
            env = child.env()
            cctx = S.EvalContext(self, n, ctx.params, ctx.outer, ctx.vars)
            cctx.row_mask = child.mask  # for subquery short-circuits
            table = child.table
            for name, expr in node.computed.items():
                v = S.eval_scalar(expr, env, cctx)
                col = _value_to_column(v, n)
                table = table.with_column(name, col)
                env[name] = S.Value(col.data, col.valid, col.dictionary)
            return MaskedTable(table, child.mask)

        if isinstance(node, R.Project):
            child = self._exec(node.child, ctx, memo)
            cols = {new: child.table.columns[old] for new, old in node.cols.items()}
            return MaskedTable(Table(cols), child.mask)

        if isinstance(node, R.Filter):
            child = self._exec(node.child, ctx, memo)
            cctx = S.EvalContext(self, child.num_rows, ctx.params, ctx.outer, ctx.vars)
            cctx.row_mask = child.mask
            v = S.eval_scalar(node.pred, child.env(), cctx)
            b = v.broadcast(child.num_rows)
            pred = b.data.astype(bool) & b.validity()  # NULL -> false
            return MaskedTable(child.table, child.mask & pred)

        if isinstance(node, R.Join):
            return self._exec_join(node, ctx, memo)

        if isinstance(node, R.Apply):
            return self._exec_apply(node, ctx, memo)

        if isinstance(node, R.GroupAgg):
            return self._exec_groupagg(node, ctx, memo)

        if isinstance(node, R.LoopScan):
            return self._exec_loopscan(node, ctx, memo)

        if isinstance(node, R.Sort):
            child = self._exec(node.child, ctx, memo)
            n = child.num_rows
            order = jnp.arange(n)
            for colname, asc in reversed(node.keys):
                col = child.table.columns[colname]
                k = _sort_key_for(col, child.mask)
                k = jnp.take(k, order)
                if not asc:
                    if jnp.issubdtype(k.dtype, jnp.floating):
                        k = jnp.where(k == _F32_MAX, k, -k)
                    else:
                        k = jnp.where(k == _I32_MAX, k, -k)
                order = jnp.take(order, jnp.argsort(k, stable=True))
            # push masked-out rows last regardless of key values
            mask_sorted = jnp.take(child.mask, order)
            order = jnp.take(order, jnp.argsort(~mask_sorted, stable=True))
            t = child.table.gather(order)
            m = jnp.take(child.mask, order)
            if node.limit is not None:
                keep = jnp.arange(n) < node.limit
                m = m & keep
            return MaskedTable(t, m)

        raise TypeError(f"unknown plan node {type(node).__name__}")

    # -- join --------------------------------------------------------------
    def _exec_join(self, node: R.Join, ctx, memo) -> MaskedTable:
        left = self._exec(node.left, ctx, memo)
        right = self._exec(node.right, ctx, memo)
        if right.num_rows == 0:
            # an empty build side matches nothing; one masked-off row keeps
            # the probe's gathers in range (jnp.take rejects an empty axis)
            right = MaskedTable(
                Table({n: Column(jnp.zeros((1,) + c.data.shape[1:], c.data.dtype),
                                 jnp.zeros((1,), bool), c.dictionary)
                       for n, c in right.table.columns.items()}),
                jnp.zeros((1,), bool))

        probe = None
        if node.dense_range is not None:
            probe = _dense_probe(node, left, right)
        ridx, hit = probe or _sorted_probe(node, left, right)

        if node.kind == "semi":
            return MaskedTable(left.table, left.mask & hit)
        if node.kind == "anti":
            return MaskedTable(left.table, left.mask & ~hit)

        rgathered = right.table.gather(ridx, valid=hit)
        cols = dict(left.table.columns)
        shared = {rc for lc, rc in node.on if lc == rc}
        rkeycols = {rc for _, rc in node.on}
        for name, col in rgathered.columns.items():
            if name in shared:
                continue
            if name in cols and name not in rkeycols:
                raise ValueError(f"join column collision: {name}")
            cols[name] = col
        mask = left.mask & hit if node.kind == "inner" else left.mask
        return MaskedTable(Table(cols), mask)

    # -- apply -------------------------------------------------------------
    def _exec_apply(self, node: R.Apply, ctx, memo) -> MaskedTable:
        left = self._exec(node.left, ctx, memo)
        n = left.num_rows
        correlated = _plan_has_outer(node.right)

        if not correlated:
            right = self._exec(node.right, ctx, memo)
            if right.num_rows != 1:
                raise NotImplementedError("uncorrelated Apply with multi-row right")
            cols = dict(left.table.columns)
            rvalid = right.mask[0]
            for name, c in right.table.columns.items():
                data = jnp.broadcast_to(c.data[0], (n,) + c.data.shape[1:])
                valid = jnp.broadcast_to(c.validity()[0] & rvalid, (n,))
                cols[name] = Column(data, valid, c.dictionary)
            return MaskedTable(Table(cols), left.mask)

        # Correlated right side rooted at ConstantScan (the algebrizer's
        # region derived-tables): evaluate its Computes directly against the
        # left columns — this is exactly "apply removal" performed at
        # execution time, fully vectorized.
        if _is_scalar_region(node.right):
            return self._exec_region_apply(node, left, ctx, memo)

        # Generic correlated apply: vmap the right plan over left rows.
        return self._exec_vmap_apply(node, left, ctx, memo)

    def _exec_region_apply(self, node, left: MaskedTable, ctx, memo) -> MaskedTable:
        """Vectorized evaluation of a single-row derived table (an algebrized
        region) against every left row at once: Outer(c) binds to the left
        column c, ColRef(c) binds to region-local computed columns.  This is
        the set-oriented execution of ``Apply`` — no per-row loop exists."""
        n = left.num_rows
        chain: list[R.RelNode] = []
        cur = node.right
        while isinstance(cur, (R.Compute, R.Project)):
            chain.append(cur)
            cur = cur.child
        assert isinstance(cur, R.ConstantScan)

        pt = None
        if node.passthrough is not None:
            v = S.eval_scalar(
                node.passthrough,
                left.env(),
                S.EvalContext(self, n, ctx.params, ctx.outer, ctx.vars),
            )
            b = v.broadcast(n)
            pt = b.data.astype(bool) & b.validity()

        outer = {**ctx.outer, **left.env()}
        env: dict[str, S.Value] = {}
        cctx = S.EvalContext(self, n, ctx.params, outer, ctx.vars)
        cctx.row_mask = left.mask
        for nd in reversed(chain):
            if isinstance(nd, R.Compute):
                for name, expr in nd.computed.items():
                    env[name] = S.eval_scalar(expr, env, cctx).broadcast(n)
            else:  # Project
                env = {new: env[old] for new, old in nd.cols.items()}

        cols = dict(left.table.columns)
        for name, v in env.items():
            b = v.broadcast(n)
            valid = b.validity()
            if pt is not None:  # pass-through rows keep NULL right side
                valid = valid & ~pt
            cols[name] = Column(b.data, valid, b.dictionary)
        return MaskedTable(Table(cols), left.mask)

    def _exec_vmap_apply(self, node, left: MaskedTable, ctx, memo) -> MaskedTable:
        n = left.num_rows
        lenv = left.env()
        names = list(lenv)
        dicts = {m: lenv[m].dictionary for m in names}

        captured_dicts: dict = {}
        # hoisted: executor state is row-independent, so building it inside
        # the traced closure would rebuild it once per traced row
        sub = self._sub_executor()

        def one_row(scalars):
            outer = {
                m: S.Value(scalars[m][0], scalars[m][1], dicts[m]) for m in names
            }
            outer = {**ctx.outer, **outer}
            res = sub.execute(node.right, params=ctx.params, outer=outer, vars=ctx.vars)
            out = {}
            for cname, c in res.table.columns.items():
                found = jnp.any(res.mask)
                idx = jnp.argmax(res.mask)
                captured_dicts[cname] = c.dictionary  # host metadata
                out[cname] = (
                    jnp.take(c.data, idx, axis=0),
                    jnp.take(c.validity(), idx) & found,
                )
            out["__exists"] = (jnp.any(res.mask), jnp.ones((), bool))
            return out

        args = {
            m: (lenv[m].broadcast(n).data, lenv[m].broadcast(n).validity())
            for m in names
        }
        mapped = jax.vmap(one_row)(args)

        if node.kind == "semi":
            return MaskedTable(left.table, left.mask & mapped["__exists"][0])
        if node.kind == "anti":
            return MaskedTable(left.table, left.mask & ~mapped["__exists"][0])

        cols = dict(left.table.columns)
        for cname, (data, valid) in mapped.items():
            if cname == "__exists":
                continue
            cols[cname] = Column(data, valid, captured_dicts.get(cname))
        mask = left.mask
        if node.kind == "cross":
            mask = mask & mapped["__exists"][0]
        return MaskedTable(Table(cols), mask)

    # -- group-by ----------------------------------------------------------
    def _exec_groupagg(self, node: R.GroupAgg, ctx, memo) -> MaskedTable:
        child = self._exec(node.child, ctx, memo)
        n = child.num_rows
        env = child.env()
        cctx = S.EvalContext(self, n, ctx.params, ctx.outer, ctx.vars)

        # Pre-evaluate aggregate input expressions (vectorized).
        agg_inputs: dict[str, S.Value] = {}
        for name, spec in node.aggs.items():
            if spec.expr is not None:
                agg_inputs[name] = S.eval_scalar(spec.expr, env, cctx).broadcast(n)

        if n == 0:
            # zero-row child (empty table or statically-empty scan): pad to
            # one all-invalid row so the reductions below keep a nonzero
            # static extent (jnp.min/.at[0] reject size 0).  The pad row is
            # masked out, so aggregates see no data and every group slot
            # comes back unoccupied — same results as a masked-empty input.
            child = MaskedTable(
                Table({
                    c: Column(
                        jnp.zeros((1,) + tuple(cc.data.shape[1:]), cc.data.dtype),
                        jnp.zeros((1,), bool), cc.dictionary,
                    )
                    for c, cc in child.table.columns.items()
                }),
                jnp.zeros((1,), bool),
            )
            agg_inputs = {
                name: S.Value(
                    jnp.zeros((1,) + tuple(v.data.shape[1:]), v.data.dtype),
                    jnp.zeros((1,), bool), v.dictionary,
                )
                for name, v in agg_inputs.items()
            }
            n = 1

        if not node.keys:
            # full-table aggregate -> single row
            cols = {}
            for name, spec in node.aggs.items():
                cols[name] = _full_agg(spec.fn, agg_inputs.get(name), child.mask)
            return MaskedTable(Table(cols), jnp.ones((1,), bool))

        # batch-mode path (paper §8.2.6): single dictionary/dense-int key and
        # matmul-friendly aggregates -> fused relagg Pallas kernel (one-hot ×
        # MXU partial aggregation; no sort)
        if self.use_pallas_agg and len(node.keys) == 1:
            out = self._try_relagg(node, child, agg_inputs)
            if out is not None:
                return out

        # stats-driven dense-key path (§Perf hillclimb 3): key densely
        # covers [lo, hi] -> gid = key - lo segmenting, NO sort
        if node.dense_range is not None and len(node.keys) == 1:
            out = self._dense_groupagg(node, child, agg_inputs)
            if out is not None:
                return out

        # sort-based grouping with static capacity
        cap = node.capacity or n
        order = jnp.arange(n)
        for k in reversed(node.keys):
            keys = _sort_key_for(child.table.columns[k], child.mask)
            keys = jnp.take(keys, order)
            order = jnp.take(order, jnp.argsort(keys, stable=True))
        mask_o = jnp.take(child.mask, order)
        order = jnp.take(order, jnp.argsort(~mask_o, stable=True))
        mask_o = jnp.take(child.mask, order)

        sorted_keys = [
            jnp.take(_sort_key_for(child.table.columns[k], child.mask), order)
            for k in node.keys
        ]
        newgrp = jnp.zeros((n,), bool).at[0].set(True)
        for sk in sorted_keys:
            newgrp = newgrp | (sk != jnp.roll(sk, 1)).at[0].set(True)
        newgrp = newgrp & mask_o
        gid = jnp.cumsum(newgrp.astype(jnp.int32)) - 1
        gid = jnp.where(mask_o, jnp.clip(gid, 0, cap - 1), cap)  # overflow slot

        num_groups = jnp.max(jnp.where(mask_o, gid, -1)) + 1
        occupied = jnp.arange(cap) < num_groups

        cols: dict[str, Column] = {}
        ones = jnp.ones((n,), jnp.float32)
        for kname in node.keys:
            kc = child.table.columns[kname]
            kdata = jnp.take(kc.data, order)
            if jnp.issubdtype(kdata.dtype, jnp.floating):
                fill = jnp.asarray(-jnp.inf, kdata.dtype)
            else:
                fill = jnp.asarray(jnp.iinfo(kdata.dtype).min, kdata.dtype)
            slot = jax.ops.segment_max(
                jnp.where(mask_o, kdata, fill), gid, num_segments=cap + 1
            )[:cap]
            cols[kname] = Column(slot, occupied, kc.dictionary)

        for name, spec in node.aggs.items():
            if spec.fn == "count_star":
                cnt = jax.ops.segment_sum(
                    jnp.where(mask_o, ones, 0.0), gid, num_segments=cap + 1
                )[:cap]
                cols[name] = Column(cnt.astype(jnp.int32), occupied)
                continue
            v = agg_inputs[name]
            data = jnp.take(v.data, order)
            vvalid = jnp.take(v.validity(), order) & mask_o
            if spec.fn in ("sum", "avg", "count"):
                s = jax.ops.segment_sum(
                    jnp.where(vvalid, data.astype(jnp.float32), 0.0),
                    gid,
                    num_segments=cap + 1,
                )[:cap]
                c = jax.ops.segment_sum(
                    jnp.where(vvalid, 1.0, 0.0), gid, num_segments=cap + 1
                )[:cap]
                if spec.fn == "sum":
                    cols[name] = Column(s, occupied & (c > 0))
                elif spec.fn == "count":
                    cols[name] = Column(c.astype(jnp.int32), occupied)
                else:
                    cols[name] = Column(
                        s / jnp.where(c == 0, 1.0, c), occupied & (c > 0)
                    )
            elif spec.fn in ("min", "max"):
                seg = jax.ops.segment_min if spec.fn == "min" else jax.ops.segment_max
                sent = jnp.inf if spec.fn == "min" else -jnp.inf
                m = seg(
                    jnp.where(vvalid, data.astype(jnp.float32), sent),
                    gid,
                    num_segments=cap + 1,
                )[:cap]
                any_v = (
                    jax.ops.segment_sum(
                        jnp.where(vvalid, 1.0, 0.0), gid, num_segments=cap + 1
                    )[:cap]
                    > 0
                )
                cols[name] = Column(m, occupied & any_v)
            else:
                raise NotImplementedError(spec.fn)
        return MaskedTable(Table(cols), occupied)

    def _dense_groupagg(self, node: R.GroupAgg, child: MaskedTable, agg_inputs):
        """Sort-free grouped aggregation for a dense int key range
        [lo, hi]: gid = key - lo, segment ops sized to the range."""
        key = node.keys[0]
        kc = child.table.columns[key]
        if not jnp.issubdtype(kc.dtype, jnp.integer):
            return None
        lo, hi = node.dense_range
        cap = hi - lo + 1
        n = child.num_rows
        gid = kc.data.astype(jnp.int32) - lo
        inside = (gid >= 0) & (gid < cap) & child.mask & kc.validity()
        gid = jnp.where(inside, gid, cap)  # overflow slot

        cols: dict[str, Column] = {}
        cnt_rows = jax.ops.segment_sum(
            inside.astype(jnp.float32), gid, num_segments=cap + 1
        )[:cap]
        occupied = cnt_rows > 0
        cols[key] = Column(
            (jnp.arange(cap, dtype=jnp.int32) + lo).astype(kc.data.dtype),
            occupied,
            kc.dictionary,
        )
        for name, spec in node.aggs.items():
            if spec.fn == "count_star":
                cols[name] = Column(cnt_rows.astype(jnp.int32), occupied)
                continue
            v = agg_inputs[name]
            vvalid = v.validity() & inside
            data = v.data
            if spec.fn in ("sum", "avg", "count"):
                s = jax.ops.segment_sum(
                    jnp.where(vvalid, data.astype(jnp.float32), 0.0),
                    gid, num_segments=cap + 1,
                )[:cap]
                c = jax.ops.segment_sum(
                    jnp.where(vvalid, 1.0, 0.0), gid, num_segments=cap + 1
                )[:cap]
                if spec.fn == "sum":
                    cols[name] = Column(s, occupied & (c > 0))
                elif spec.fn == "count":
                    cols[name] = Column(c.astype(jnp.int32), occupied)
                else:
                    cols[name] = Column(
                        s / jnp.where(c == 0, 1.0, c), occupied & (c > 0)
                    )
            elif spec.fn in ("min", "max"):
                seg = jax.ops.segment_min if spec.fn == "min" else jax.ops.segment_max
                sent = jnp.inf if spec.fn == "min" else -jnp.inf
                m = seg(
                    jnp.where(vvalid, data.astype(jnp.float32), sent),
                    gid, num_segments=cap + 1,
                )[:cap]
                any_v = jax.ops.segment_sum(
                    jnp.where(vvalid, 1.0, 0.0), gid, num_segments=cap + 1
                )[:cap] > 0
                cols[name] = Column(m, occupied & any_v)
            else:
                return None
        return MaskedTable(Table(cols), occupied)

    def _try_relagg(self, node: R.GroupAgg, child: MaskedTable, agg_inputs):
        """Fused group-by via the relagg kernel.  Applicable when the key is
        dictionary-encoded (G = vocab size) or a capacity hint bounds a
        non-negative int key, G is within the kernel's VMEM bound
        (``relagg.MAX_GROUPS``), and all aggs are sum/avg/count/count_star."""
        from repro.kernels.relagg.ops import grouped_aggregate
        from repro.kernels.relagg.relagg import MAX_GROUPS

        key = node.keys[0]
        kc = child.table.columns[key]
        if kc.dictionary is not None:
            G = len(kc.dictionary)
        elif node.capacity is not None and jnp.issubdtype(kc.dtype, jnp.integer):
            G = int(node.capacity)
        else:
            return None
        if not 1 <= G <= MAX_GROUPS:
            return None
        if not all(a.fn in ("sum", "avg", "count", "count_star")
                   for a in node.aggs.values()):
            return None

        n = child.num_rows
        mask = child.mask & kc.validity() & (kc.data >= 0) & (kc.data < G)
        cols_spec: list[tuple[str, str, int, int]] = []  # (name, fn, vi, ci)
        mats = []
        for name, spec in node.aggs.items():
            if spec.fn == "count_star":
                cols_spec.append((name, spec.fn, -1, -1))
                continue
            v = agg_inputs[name]
            vv = v.validity()
            data = jnp.where(vv, v.data.astype(jnp.float32), 0.0)
            mats.append(data)
            vi = len(mats) - 1
            mats.append(jnp.where(vv, 1.0, 0.0))  # per-agg valid count
            cols_spec.append((name, spec.fn, vi, vi + 1))
        vals = (
            jnp.stack(mats, axis=1)
            if mats
            else jnp.zeros((n, 1), jnp.float32)
        )
        sums, counts = grouped_aggregate(
            kc.data.astype(jnp.int32), mask, vals, G
        )
        self._stats["relagg_groupaggs"] = (
            self._stats.get("relagg_groupaggs", 0) + 1)
        occupied = counts > 0
        out_cols: dict[str, Column] = {
            key: Column(jnp.arange(G, dtype=kc.data.dtype), occupied, kc.dictionary)
        }
        for name, fn, vi, ci in cols_spec:
            if fn == "count_star":
                out_cols[name] = Column(counts.astype(jnp.int32), occupied)
            elif fn == "count":
                out_cols[name] = Column(sums[:, ci].astype(jnp.int32), occupied)
            elif fn == "sum":
                out_cols[name] = Column(sums[:, vi], occupied & (sums[:, ci] > 0))
            else:  # avg
                c = sums[:, ci]
                out_cols[name] = Column(
                    sums[:, vi] / jnp.where(c == 0, 1.0, c),
                    occupied & (c > 0),
                )
        return MaskedTable(Table(out_cols), occupied)

    # -- loop scan (rewritten cursor loops, repro.loops) --------------------
    def _exec_loopscan(self, node: R.LoopScan, ctx, memo) -> MaskedTable:
        child = self._exec(node.child, ctx, memo)
        n = child.num_rows
        ictx = S.EvalContext(self, 1, ctx.params, ctx.outer, ctx.vars)
        init = {
            name: _scalar_value(S.eval_scalar(e, {}, ictx))
            for name, e in node.carry.items()
        }
        if node.kind == "reduce":
            return self._loopscan_reduce(node, child, init, ctx)
        return self._loopscan_scan(node, child, init, ctx)

    def _loopscan_reduce(self, node, child, init, ctx) -> MaskedTable:
        """Commutative fold: masked sum/prod over the whole relation —
        no sequential dependence, fully vectorized."""
        n = child.num_rows
        env = child.env()
        cctx = S.EvalContext(self, n, ctx.params, ctx.outer, ctx.vars)
        cctx.row_mask = child.mask
        active = child.mask
        cols: dict[str, Column] = {}
        for name in node.outputs:
            mode, op, term, pred = node.reductions[name]
            iv = init[name]
            if mode == "last":
                # final fetch-variable value: the last active row's column
                # (or the loop-entry value when the cursor is empty)
                col = child.table.columns[op]
                if n == 0:
                    out = iv
                else:
                    has = jnp.any(active)
                    idx = (n - 1) - jnp.argmax(active[::-1])
                    out = S.Value(
                        jnp.where(has, jnp.take(col.data, idx, axis=0),
                                  iv.data.astype(col.data.dtype)),
                        jnp.where(has, jnp.take(col.validity(), idx),
                                  iv.validity()),
                        col.dictionary,
                    )
            else:  # fold
                tv = S.eval_scalar(term, env, cctx).broadcast(max(n, 1))
                g = active
                if pred is not None:
                    pv = S.eval_scalar(pred, env, cctx).broadcast(max(n, 1))
                    g = g & pv.data.astype(bool) & pv.validity()
                common = jnp.result_type(iv.data.dtype, tv.data.dtype)
                td = tv.data.astype(common)
                if n == 0:
                    out = iv
                elif op == "+":
                    out = S.Value(
                        iv.data.astype(common)
                        + jnp.sum(jnp.where(g, td, jnp.zeros((), common))),
                        # NULL is sticky: any accumulated NULL term poisons
                        # the fold, matching per-row +/* NULL propagation
                        iv.validity() & ~jnp.any(g & ~tv.validity()),
                    )
                else:  # "*"
                    out = S.Value(
                        iv.data.astype(common)
                        * jnp.prod(jnp.where(g, td, jnp.ones((), common))),
                        iv.validity() & ~jnp.any(g & ~tv.validity()),
                    )
            cols[name] = _value_to_column(_scalar_value(out), 1)
        return MaskedTable(Table(cols), jnp.ones((1,), bool))

    def _loopscan_scan(self, node, child, init, ctx) -> MaskedTable:
        """Order-dependent fold: ``lax.scan`` over the relation's rows,
        evaluating the predicated step list per row.  Masked-out rows are
        skipped (their steps see ``__live`` false); ``__done`` makes BREAK
        and failed guards sticky."""
        from repro.loops.rewrite import DONE, LIVE

        dicts = {c: col.dictionary for c, col in child.table.columns.items()}
        col_arrays = {
            c: (col.data, col.validity())
            for c, col in child.table.columns.items()
        }
        init_leaves = {
            name: (v.data, v.validity()) for name, v in init.items()
        }

        def step(carry, xs):
            mask_bit, row_cols = xs
            done = carry[DONE][0]
            vars_env = {
                name: S.Value(d, v) for name, (d, v) in carry.items()
            }
            vars_env[LIVE] = S.Value(mask_bit & ~done)
            env = {
                c: S.Value(d, v, dicts[c]) for c, (d, v) in row_cols.items()
            }
            sctx = S.EvalContext(executor=self, num_rows=1,
                                 params=ctx.params, outer=ctx.outer,
                                 vars=vars_env)
            for name, expr in node.steps:
                vars_env[name] = S.eval_scalar(expr, env, sctx)
            out = {}
            for name, (d0, v0) in carry.items():
                nv = _scalar_value(vars_env[name])
                # cast back to the loop-entry dtype: the carry structure
                # must be invariant across scan iterations
                out[name] = (nv.data.astype(d0.dtype), nv.validity())
            return out, None

        final, _ = jax.lax.scan(step, init_leaves, (child.mask, col_arrays))
        cols = {
            name: Column(final[name][0][None], final[name][1][None])
            for name in node.outputs
        }
        return MaskedTable(Table(cols), jnp.ones((1,), bool))

    # -- scalar-subquery hooks (called from scalar.eval_scalar) -------------
    def eval_scalar_subquery(self, expr: S.ScalarSubquery, env, ctx) -> S.Value:
        correlated = _plan_has_outer(expr.plan)
        if not correlated:
            res = self.execute(expr.plan, params=ctx.params, outer=ctx.outer, vars=ctx.vars)
            return _extract_scalar(res, expr.column)
        # correlated: vmap the whole subplan over outer rows
        n = ctx.num_rows
        names = sorted(
            _plan_outer_refs(expr.plan) & set(env.keys() | ctx.outer.keys())
        )
        dicts = {}
        cols = {}
        for m in names:
            v = env.get(m, ctx.outer.get(m))
            b = v.broadcast(n)
            cols[m] = (b.data, b.validity())
            dicts[m] = v.dictionary

        captured: dict = {}
        sub = self._sub_executor()

        def one(scalars):
            outer = {m: S.Value(scalars[m][0], scalars[m][1], dicts[m]) for m in names}
            outer = {**ctx.outer, **outer}
            res = sub.execute(expr.plan, params=ctx.params, outer=outer, vars=ctx.vars)
            v = _extract_scalar(res, expr.column)
            captured["dict"] = v.dictionary  # host metadata, set at trace time
            return v.data, v.validity()

        data, valid = jax.vmap(one)(cols)
        return S.Value(data, valid, captured.get("dict"))

    def eval_exists(self, expr: S.Exists, env, ctx) -> S.Value:
        correlated = _plan_has_outer(expr.plan)
        if not correlated:
            res = self.execute(expr.plan, params=ctx.params, outer=ctx.outer, vars=ctx.vars)
            v = jnp.any(res.mask)
            return S.Value(~v if expr.negated else v)
        n = ctx.num_rows
        names = sorted(
            _plan_outer_refs(expr.plan) & set(env.keys() | ctx.outer.keys())
        )
        dicts = {m: env.get(m, ctx.outer.get(m)).dictionary for m in names}
        cols = {}
        for m in names:
            v = env.get(m, ctx.outer.get(m))
            b = v.broadcast(n)
            cols[m] = (b.data, b.validity())

        sub = self._sub_executor()

        def one(scalars):
            outer = {m: S.Value(scalars[m][0], scalars[m][1], dicts[m]) for m in names}
            outer = {**ctx.outer, **outer}
            res = sub.execute(expr.plan, params=ctx.params, outer=outer, vars=ctx.vars)
            return jnp.any(res.mask)

        data = jax.vmap(one)(cols)
        return S.Value(~data if expr.negated else data)

    def eval_udf_call(self, expr: S.UdfCall, env, ctx) -> S.Value:
        if self.udf_column_evaluator is None:
            raise RuntimeError(
                f"UDF {expr.name!r} not inlined and no iterative evaluator "
                "attached (enable froid, or run via the interpreter)"
            )
        with jax.named_scope("froid.udf"):
            return self.udf_column_evaluator(expr, env, ctx)


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _key_sentinel(keys: jnp.ndarray):
    return _F32_MAX if jnp.issubdtype(keys.dtype, jnp.floating) else _I32_MAX


def _sorted_probe(node: R.Join, left: MaskedTable, right: MaskedTable):
    """``(build row, hit)`` per probe row by sort-merge: a stable argsort
    of the build keys and a left ``searchsorted`` of the probe keys, so
    among equal build keys the lowest-numbered row wins."""
    if len(node.on) == 1:
        lcol, rcol = node.on[0]
        lkeys = _sort_key_for(left.table.columns[lcol], left.mask)
        rkeys = _sort_key_for(right.table.columns[rcol], right.mask)
    else:
        # composite keys: dense-rank the union of both sides' key
        # tuples into one synthetic int32 key, then merge as usual
        lkeys, rkeys = _union_dense_rank(left, right, node.on)
    perm = jnp.argsort(rkeys, stable=True)
    sorted_keys = jnp.take(rkeys, perm)

    pos = jnp.searchsorted(sorted_keys, lkeys)
    pos = jnp.clip(pos, 0, sorted_keys.shape[0] - 1)
    hit = (jnp.take(sorted_keys, pos) == lkeys) & (lkeys != _key_sentinel(lkeys))
    return jnp.take(perm, pos), hit


def _dense_probe(node: R.Join, left: MaskedTable, right: MaskedTable):
    """``(build row, hit)`` per probe row by direct address over the key
    range ``node.dense_range``: a scatter-min of build row numbers into one
    slot per key value picks the lowest-numbered live row of each key, as
    :func:`_sorted_probe` does.  None where a key column is not of an
    integer type (the sorted probe then runs)."""
    (lcol, rcol), = node.on
    lk, rk = left.table.columns[lcol], right.table.columns[rcol]
    if not (jnp.issubdtype(lk.dtype, jnp.integer)
            and jnp.issubdtype(rk.dtype, jnp.integer)):
        return None
    lo, hi = node.dense_range
    span, nr = hi - lo + 1, right.num_rows
    # compared before subtracting, so no key outside [lo, hi] can wrap in
    rkey = rk.data.astype(jnp.int32)
    rlive = right.mask & rk.validity() & (rkey >= lo) & (rkey <= hi)
    slot = jnp.full((span,), nr, jnp.int32).at[
        jnp.where(rlive, rkey - lo, span)].min(
        jnp.arange(nr, dtype=jnp.int32), mode="drop")
    lkey = lk.data.astype(jnp.int32)
    inside = left.mask & lk.validity() & (lkey >= lo) & (lkey <= hi)
    ridx = jnp.take(slot, jnp.where(inside, lkey - lo, 0), mode="clip")
    return ridx, inside & (ridx < nr)


def _full_agg(fn: str, v: S.Value | None, mask: jnp.ndarray) -> Column:
    n = mask.shape[0]
    if fn == "count_star":
        return Column(jnp.sum(mask).astype(jnp.int32)[None], jnp.ones((1,), bool))
    assert v is not None
    sel = mask & v.validity()
    data = v.data
    if fn == "count":
        return Column(jnp.sum(sel).astype(jnp.int32)[None], jnp.ones((1,), bool))
    if fn == "sum":
        s = jnp.sum(jnp.where(sel, data.astype(jnp.float32), 0.0))
        return Column(s[None], jnp.any(sel)[None])
    if fn == "avg":
        s = jnp.sum(jnp.where(sel, data.astype(jnp.float32), 0.0))
        c = jnp.sum(sel)
        return Column((s / jnp.where(c == 0, 1, c))[None], (c > 0)[None])
    if fn == "min":
        m = jnp.min(jnp.where(sel, data.astype(jnp.float32), jnp.inf))
        return Column(m[None], jnp.any(sel)[None])
    if fn == "max":
        m = jnp.max(jnp.where(sel, data.astype(jnp.float32), -jnp.inf))
        return Column(m[None], jnp.any(sel)[None])
    raise NotImplementedError(fn)


def _extract_scalar(res: MaskedTable, column: str | None) -> S.Value:
    names = res.table.names()
    if column is None:
        if len(names) != 1:
            raise ValueError(f"scalar subquery must produce 1 column, got {names}")
        column = names[0]
    c = res.table.columns[column]
    found = jnp.any(res.mask)
    idx = jnp.argmax(res.mask)
    return S.Value(
        jnp.take(c.data, idx, axis=0),
        jnp.take(c.validity(), idx) & found,
        c.dictionary,
    )


def _plan_has_outer(plan: R.RelNode) -> bool:
    return len(_plan_outer_refs(plan)) > 0


def _plan_outer_refs(plan: R.RelNode) -> set[str]:
    out: set[str] = set()
    for node in R.walk_plan(plan):
        for e in node.exprs():
            out |= S.free_outer(e)
        if isinstance(node, R.Compute):
            for e in node.computed.values():
                out |= S.free_outer(e)
                for sub in S.walk(e):
                    if isinstance(sub, (S.ScalarSubquery, S.Exists)):
                        out |= _plan_outer_refs(sub.plan)
        for e in node.exprs():
            for sub in S.walk(e):
                if isinstance(sub, (S.ScalarSubquery, S.Exists)):
                    out |= _plan_outer_refs(sub.plan)
    return out


def _is_scalar_region(plan: R.RelNode) -> bool:
    """True if ``plan`` is Compute/Project/Filter-over-ConstantScan — i.e. a
    single-row derived table (an algebrized region)."""
    node = plan
    while isinstance(node, (R.Compute, R.Project)):
        node = node.child
    return isinstance(node, R.ConstantScan)

