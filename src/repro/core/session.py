"""Session / PreparedStatement: the engine's prepare-once-execute-many API.

The paper's economics (PVLDB 11(4)) come from planning a UDF-bearing query
*once* and running the set-oriented plan many times.  This module is that
lifecycle as an API:

* :class:`Session` owns the catalog + UDF registry and two caches — a
  **plan cache** (bound + optimized plans, keyed by query fingerprint ×
  policy × catalog/registry state) and an **executable cache** (whole-plan
  jitted callables, additionally keyed by the parameter signature).
* :class:`PreparedStatement` is the client handle: ``prepare`` plans and
  binds (cold); ``execute(params=…)`` runs warm off the cached jitted
  callable — changed parameter *values* ride the same executable, only a
  changed parameter *signature* (dtype/shape/string) re-specializes.
* :class:`QueryResult` reports rows lazily plus the plan, explain text,
  public engine stats and whether the call was served from cache.

Cache invalidation is by content: the catalog/registry tokens cover both
``create_table``/``create_function`` and direct ``catalog[...] =`` pokes
(benchmarks do this), so DDL or data replacement re-plans on next use.
"""
from __future__ import annotations

import dataclasses
import hashlib
import itertools
import threading
import time
import warnings
from collections import OrderedDict, deque
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import optimizer as O
from repro.core import relalg as R
from repro.core import scalar as S
from repro.core.binder import Binder, InlineConstraints
from repro.core.executor import Executor, MaskedTable
from repro.core.frontend import Q
from repro.core.interpreter import Interpreter
from repro.core.ir import UdfDef
from repro.core.policy import FROID, ExecutionPolicy, resolve_policy
from repro.tables.table import Column, DictEncoding, Table
from repro.telemetry import CATALOG_EVENT, span


# ---------------------------------------------------------------------------
# structural fingerprints (cache keys) — canonical home is
# repro.core.fingerprint (below the optimizer in the import graph, so the
# decorrelation pass's shared-build dedup can fingerprint subtrees without a
# cycle); the names stay re-exported here for the original import surface.
# ---------------------------------------------------------------------------

from repro.core.fingerprint import (  # noqa: E402,F401  (re-exports)
    _expr_key,
    _norm,
    const_hole_key,
    liftable_const,
    parametric_fingerprint,
    plan_fingerprint,
)


# ---------------------------------------------------------------------------
# results
# ---------------------------------------------------------------------------


class QueryResult:
    """Result of one execution.

    ``table`` (compacted host-visible rows) materializes lazily — the
    masked device form is the primary product, so timing loops that only
    touch ``masked`` never pay the host gather.

    Batched/async executions defer even the masked form: they pass
    ``materialize`` instead of ``masked``, and the first ``masked`` access
    slices this call's rows out of the shared device batch (or syncs the
    in-flight dispatch).  Until then the result is a stats-and-plan shell,
    so fan-out paths never pay O(batch) per-result slicing up front.
    """

    def __init__(self, masked: MaskedTable | None, plan: R.RelNode,
                 elapsed_s: float, stats: dict,
                 policy: ExecutionPolicy | None = None,
                 cache_hit: bool = False, materialize=None):
        if masked is None and materialize is None:
            raise ValueError("QueryResult needs masked or materialize")
        self._masked = masked
        self._materialize = materialize
        self.plan = plan
        self.elapsed_s = elapsed_s
        self.stats = stats
        self.policy = policy
        self.cache_hit = cache_hit
        self._table: Table | None = None

    @property
    def masked(self) -> MaskedTable:
        if self._masked is None:
            self._masked = self._materialize()
            self._materialize = None
        return self._masked

    @property
    def table(self) -> Table:
        if self._table is None:
            self._table = self.masked.compact()
        return self._table

    @property
    def explain(self) -> str:
        return O.explain(self.plan)

    def __repr__(self):
        pol = self.policy.name if self.policy else "?"
        return (f"QueryResult(rows={self.masked.num_rows}, policy={pol}, "
                f"cache_hit={self.cache_hit}, elapsed_s={self.elapsed_s:.4f})")


class AsyncResult:
    """Future returned by :meth:`PreparedStatement.execute_async`.

    The device call is already dispatched; ``result()`` blocks until the
    outputs are ready and returns the :class:`QueryResult`.  ``done()``
    polls readiness without blocking, so callers can pipeline host work
    against device compute.

    A truly-async result occupies one of the session's bounded in-flight
    slots (``policy.max_inflight``) until ``result()`` syncs it — the
    backpressure that keeps a runaway producer from queueing unbounded
    device work.  Degraded (synchronous) results never hold a slot.
    """

    def __init__(self, result: QueryResult, marker=None, session=None):
        self._result = result
        self._marker = marker  # a device array from the in-flight dispatch
        self._session = session
        self._released = session is None

    def done(self) -> bool:
        m = self._marker
        if m is None or not hasattr(m, "is_ready"):
            return True
        return m.is_ready()

    def _release(self) -> None:
        if self._released:
            return
        self._released = True
        try:
            self._session._inflight.remove(self)
        except ValueError:
            pass  # already reaped by a later dispatch's admission pass

    def result(self) -> QueryResult:
        _ = self._result.masked  # forces sync + materialization
        self._release()
        return self._result

    def __repr__(self):
        return f"AsyncResult(done={self.done()})"


#: backward-compatible alias — the old Database.run result type
RunResult = QueryResult


# monotonic stamps for cache tokens: attached to catalog/registry objects
# the first time the session sees them, so a *new* object always gets a new
# stamp even if the allocator reuses a dead object's address (id() alone is
# unsafe as a cache key once the old object is garbage)
_stamps = itertools.count(1)


def _stamp(obj) -> int:
    s = getattr(obj, "_session_stamp", None)
    if s is None:
        s = next(_stamps)
        try:
            obj._session_stamp = s
        except AttributeError:  # frozen dataclass
            object.__setattr__(obj, "_session_stamp", s)
    return s


def _table_content_digest(t: Table) -> str:
    """Value digest of one table: per-column name/dtype/shape/vocab plus the
    raw data and validity bytes.  Cached on the table object — the same
    invalidation model as :func:`_stamp` (replace the Table, get a fresh
    digest), but the digest is *content-derived*, so two processes loading
    identical data agree on it.  This is what makes persistent cache keys
    meaningful across workers: a stamp says "some table object #17", a
    digest says "this exact data"."""
    d = getattr(t, "_content_digest", None)
    if d is None:
        h = hashlib.sha1()
        for name, col in sorted(t.columns.items()):
            arr = np.asarray(col.data)
            h.update(repr((name, str(arr.dtype), arr.shape,
                           _vocab(col.dictionary))).encode())
            h.update(arr.tobytes())
            h.update(np.asarray(col.validity()).tobytes())
        d = h.hexdigest()
        try:
            t._content_digest = d
        except AttributeError:
            pass
    return d


def _udf_content_digest(u: UdfDef) -> str:
    """Structural digest of a UDF definition (via :func:`_norm`), cached on
    the object; the registry half of the content-derived env token."""
    d = getattr(u, "_content_digest", None)
    if d is None:
        d = hashlib.sha1(repr(_norm(u)).encode()).hexdigest()
        try:
            u._content_digest = d
        except AttributeError:
            object.__setattr__(u, "_content_digest", d)
    return d


class _BoundedCache(OrderedDict):
    """Insertion-ordered dict evicting the least-recently-used entry past
    ``cap`` — per-tick table reloads would otherwise grow the plan and
    executable caches without bound in long-running serving loops."""

    def __init__(self, cap: int):
        super().__init__()
        self.cap = cap

    def get(self, key, default=None):
        v = super().get(key, default)
        if key in self:
            self.move_to_end(key)
        return v

    def __setitem__(self, key, value):
        super().__setitem__(key, value)
        self.move_to_end(key)
        while len(self) > self.cap:
            self.popitem(last=False)


# ---------------------------------------------------------------------------
# parameter handling
# ---------------------------------------------------------------------------


def _param_value(v) -> S.Value:
    if isinstance(v, S.Value):
        return v
    if isinstance(v, str):
        return S.Value(jnp.asarray(0, jnp.int32), None, DictEncoding([v]))
    if isinstance(v, bool):
        return S.Value(jnp.asarray(v, bool))
    if isinstance(v, (int, np.integer)):
        return S.Value(jnp.asarray(v, jnp.int32))
    if isinstance(v, (float, np.floating)):
        return S.Value(jnp.asarray(v, jnp.float32))
    arr = jnp.asarray(v)
    if arr.dtype == jnp.float64:
        arr = arr.astype(jnp.float32)
    if arr.dtype == jnp.int64:
        arr = arr.astype(jnp.int32)
    return S.Value(arr)


_SIG_DTYPES = {"float64": "float32", "int64": "int32"}


def param_signature(params: dict | None) -> tuple:
    """The shape of a parameter set: names, dtypes, shapes — and for
    strings the value itself (the dictionary is host-side metadata baked
    into the trace).  Value changes within a signature never re-plan.
    Computed host-side: no device arrays are created here (the hot path
    calls this on every execute)."""
    if not params:
        return ()
    out = []
    for name in sorted(params):
        v = params[name]
        if isinstance(v, str):
            out.append((name, "str", v))
        elif isinstance(v, S.Value):
            # the dictionary is baked into the trace as host metadata, so
            # it is part of the signature (same codes, different vocab
            # would otherwise warm-hit the wrong executable)
            out.append((name, str(v.data.dtype), tuple(v.data.shape),
                        _vocab(v.dictionary)))
        elif isinstance(v, bool):
            out.append((name, "bool", ()))
        elif isinstance(v, (int, np.integer)):
            out.append((name, "int32", ()))
        elif isinstance(v, (float, np.floating)):
            out.append((name, "float32", ()))
        elif hasattr(v, "dtype") and hasattr(v, "shape"):
            dt = str(v.dtype)
            out.append((name, _SIG_DTYPES.get(dt, dt), tuple(v.shape)))
        else:
            arr = np.asarray(v)
            dt = str(arr.dtype)
            out.append((name, _SIG_DTYPES.get(dt, dt), tuple(arr.shape)))
    return tuple(out)


def batch_bucket(n: int, max_batch: int) -> int:
    """Device batch size for ``n`` same-signature param sets: the next
    power of two, capped at ``max_batch``.  Bucketing means a statement
    executed at N = 5, 6, 7 … shares one vmapped executable (padded to 8)
    instead of re-specializing per distinct N."""
    if n <= 0:
        raise ValueError("batch of zero parameter sets")
    b = 1
    while b < n:
        b <<= 1
    return max(1, min(b, max_batch))


#: distinct-binding counts at or below this threshold keep exact template
#: pools; above it the pool pads to the next power of two.  Small pools
#: re-jit rarely and padding them is pure waste; large growing binding
#: populations would otherwise re-specialize the fused program once per
#: distinct d (the CSE d-churn bug) — bucketing bounds that to O(log d).
#: Benchmarks monkeypatch this to measure both arms (BENCH_pr8 justifies
#: the cutoff with the padded-pool overhead numbers).
CSE_EXACT_D = 8


def _pool_pad(d: int) -> int:
    """Template-pool slot count for ``d`` distinct bindings: exact at or
    below :data:`CSE_EXACT_D`, the next power of two above it.  Padded
    slots repeat the last real binding and are computed-then-ignored,
    exactly like batch-bucket padding rows — no ticket's slot index ever
    references one."""
    if d <= CSE_EXACT_D:
        return d
    b = 1
    while b < d:
        b <<= 1
    return b


def _stack_params(params_list: list[dict], host: bool = False) -> dict:
    """Stack same-signature param dicts into one batched argument pytree:
    name -> (data (B, …), valid (B, …)).  Scalars take the numpy fast path
    (one host array per name, not B device scalars); with ``host`` they
    stay on the host, for a caller that places them on a mesh itself (one
    transfer to each device, not one to the default device and a
    reshard)."""
    xp = np if host else jnp
    first = params_list[0]
    out = {}
    for name in sorted(first):
        vs = [p[name] for p in params_list]
        v0 = vs[0]
        if isinstance(v0, bool):
            data = xp.asarray(np.asarray(vs, dtype=bool))
        elif isinstance(v0, (int, np.integer)):
            data = xp.asarray(np.asarray(vs), np.int32)
        elif isinstance(v0, (float, np.floating)):
            data = xp.asarray(np.asarray(vs), np.float32)
        else:
            vals = [_param_value(v) for v in vs]
            out[name] = (
                jnp.stack([v.data for v in vals]),
                jnp.stack([v.validity() for v in vals]),
            )
            continue
        out[name] = (data, xp.ones((len(vs),), bool))
    return out


def _batched_avals(params0: dict, bucket: int) -> dict:
    """Abstract (shape, dtype) pytree of a :func:`_stack_params` batch of
    ``bucket`` tickets shaped like ``params0`` — what the persistent tier's
    AOT lower runs against, without materializing ``bucket`` param copies.
    Stacking two copies (not one) keeps every leaf's per-ticket trailing
    shape explicit, then the leading axis is rewritten to the bucket."""
    if not params0:
        return {}
    ex = _stack_params([params0, params0])
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct((bucket,) + tuple(x.shape[1:]),
                                       x.dtype),
        ex)


def _vocab(dictionary) -> tuple | None:
    """Host tuple of a DictEncoding's contents (shared by the signature
    and binding-key paths)."""
    if dictionary is None:
        return None
    return tuple(dictionary.decode(i) for i in range(len(dictionary)))


def _binding_key(v) -> tuple:
    """Hashable identity of one parameter value — the dedup key of the
    template binding pools (value-level, unlike :func:`param_signature`
    which deliberately erases values for numeric params).  ``S.Value``
    bindings cost a device→host read, so their key is memoized on the
    instance — repeated tickets carrying the same Value object sync
    once, not once per ticket."""
    if isinstance(v, S.Value):
        cached = getattr(v, "_binding_key_cache", None)
        if cached is not None:
            return cached
        arr = np.asarray(v.data)
        valid = None if v.valid is None else np.asarray(v.valid).tobytes()
        key = ("value", str(arr.dtype), arr.shape, arr.tobytes(), valid,
               _vocab(v.dictionary))
        v._binding_key_cache = key
        return key
    if isinstance(v, str):
        return ("str", v)
    if isinstance(v, bool):
        return ("bool", v)
    if isinstance(v, (int, np.integer)):
        return ("int", int(v))
    if isinstance(v, (float, np.floating)):
        # bit-pattern identity at the executed precision: -0.0 must not
        # dedup against 0.0 (sign-sensitive templates would answer with
        # the wrong sign of infinity), and NaN must dedup against itself
        # (value equality would mint a fresh pool slot per NaN ticket)
        return ("float", np.float32(float(v)).tobytes())
    arr = np.asarray(v)
    return ("array", str(arr.dtype), arr.shape, arr.tobytes())


def _maximal_cse_occurrences(merged, plan) -> list:
    """Template occurrences of ``plan`` that actually execute in a member's
    trace: top-down, stopping at the first marked node (a shared-constant
    or template mark) — everything beneath it is answered from a pool and
    never runs, so nested occurrences must not open pool groups of their
    own.  Memoized on the (cached, immutable) FusedPlan per member plan —
    warm drains must not re-walk plans they have already planned."""
    cache = getattr(merged, "_occ_cache", None)
    if cache is None:
        cache = merged._occ_cache = {}
    # entries hold the plan itself, so a hit is identity-verified — an
    # id() recycled onto a different plan object can never match
    hit = cache.get(id(plan))
    if hit is not None and hit[0] is plan:
        return hit[1]
    out = []

    def visit(n):
        nid = n.node_id
        if nid in merged.template_ids:
            out.append(n)
            return
        if nid in merged.shared_ids:
            return  # answered from the constant pool; nothing below runs
        for p in R.embedded_plans(n):
            visit(p)
        for c in n.children():
            visit(c)

    visit(plan)
    cache[id(plan)] = (plan, out)
    return out


def _plan_template_groups(merged, members, params_by_member):
    """Host-side binding planning for a fused wave.

    For every maximal template occurrence of every batched member, group by
    (template fingerprint, binding signature) into a :class:`_PoolGroup`,
    dedup the tickets' hole-value tuples into the group's distinct-binding
    list, and record each ticket's pool slot.  Returns ``(groups,
    member_tmaps, slot_maps, slot_names, template_token)`` where
    ``member_tmaps[i]`` maps occurrence ``node_id -> group index`` for
    member ``i``, ``slot_maps[i]`` maps ``node_id -> [slot per ticket]``,
    ``slot_names[i]`` maps ``node_id -> reserved slot-parameter name``
    (the occurrence's *ordinal* within this walk — deterministic from the
    plan structure, so the fused program's argument pytree spells
    identically in every process and AOT-compiled programs round-trip
    through the persistent tier), and ``template_token`` — ``((fp, sig,
    pool_pad(d)), ...)`` in group order — is the template identity the
    fused cache key incorporates (members arrive canonically sorted, so
    the token is arrival-order independent; ``d`` is bucketed by
    :func:`_pool_pad` so a growing distinct-binding population
    re-specializes O(log d) times, not per distinct d)."""
    from repro.fuse.merge import CONST_BIND, slot_param

    def hole_value(bind_h, pdict):
        """``(supplied, value)`` of one hole: const-bind markers carry the
        literal value; param binds look up the ticket's params."""
        if isinstance(bind_h, tuple) and bind_h[0] == CONST_BIND:
            return True, bind_h[1]
        if bind_h not in pdict:
            return False, None
        return True, pdict[bind_h]

    by_fp = {t.fp: t for t in merged.templates}
    groups: list[_PoolGroup] = []
    gindex: dict[tuple, int] = {}
    member_tmaps: list[dict] = []
    slot_maps: list[dict] = []
    slot_names: list[dict] = []
    for m, plist in zip(members, params_by_member):
        tmap: dict[int, int] = {}
        smap: dict[int, list] = {}
        names: dict[int, str] = {}
        # parameter-free members still pool occurrences whose holes are all
        # const-bound (lifted templates) — their slot rides as an unbatched
        # reserved parameter
        if plist:
            pdict0 = plist[0] or {}
            for n in _maximal_cse_occurrences(merged, m.plan):
                fp = merged.template_ids[n.node_id]
                bind = merged.template_binds[n.node_id]
                tmpl = by_fp[fp]
                # an occurrence whose actual parameters are not all
                # supplied cannot be pooled; the member trace will raise
                # (or not reach it) exactly as the per-statement path would
                vals0 = {}
                for h in tmpl.holes:
                    ok, v = hole_value(bind[h], pdict0)
                    if not ok:
                        vals0 = None
                        break
                    vals0[h] = v
                if vals0 is None:
                    continue
                sig = param_signature(vals0)
                gk = (fp, sig)
                gi = gindex.get(gk)
                if gi is None:
                    gi = gindex[gk] = len(groups)
                    groups.append(_PoolGroup(
                        fp, sig, tmpl.node, tmpl.holes,
                        {h: _param_value(vals0[h]).dictionary
                         for h in tmpl.holes},
                        [], {},
                    ))
                g = groups[gi]
                slots = []
                for p in plist:
                    pd = p or {}
                    b = {h: hole_value(bind[h], pd)[1] for h in tmpl.holes}
                    key = tuple(_binding_key(b[h]) for h in tmpl.holes)
                    slot = g.index.get(key)
                    if slot is None:
                        slot = g.index[key] = len(g.bindings)
                        g.bindings.append(b)
                    slots.append(slot)
                tmap[n.node_id] = gi
                smap[n.node_id] = slots
                # canonical spelling: the ordinal among this member's
                # pooled occurrences (walk order is plan-structural and
                # the pooled subset is a function of the member's param
                # signature, so the name set — and with it the fused
                # argument pytree — reproduces exactly across processes)
                names[n.node_id] = slot_param(len(names))
        member_tmaps.append(tmap)
        slot_maps.append(smap)
        slot_names.append(names)
    # the cache token carries the *padded* pool size: binding counts that
    # land in the same d-bucket share one fused specialization (the exact
    # count still rides per-wave as cse_bindings in the stats)
    token = tuple((g.fp, g.sig, _pool_pad(len(g.bindings))) for g in groups)
    return groups, member_tmaps, slot_maps, slot_names, token


# ---------------------------------------------------------------------------
# compiled executables
# ---------------------------------------------------------------------------


class _Program:
    """A compiled program split where the host's work meets the device's:
    ``args(...)`` packs the call's arguments (parameters, catalog arrays,
    their placement) and ``target`` runs them.  ``source`` says where
    ``target`` came from: ``"aot"`` (lowered and compiled at the cache
    miss), ``"store"`` (loaded from the ``PlanStore``) or ``"jit"``
    (compiled by its first call)."""

    #: whether a lazily jitted ``target`` has run, and so compiled
    ran = False


@dataclasses.dataclass
class _Executable(_Program):
    args: Any  # (param_values, catalog_token) -> (table_args, param_args)
    target: Any  # the program over those arguments
    plan: R.RelNode
    out_dicts: dict  # column name -> DictEncoding | None (trace-time capture)
    stats: dict  # trace-time logical reads of one execution
    raw: Any = None  # untraced (table_args, param_args) closure (vmap source)
    #: the AOT ``jax.stages.Compiled`` when one was built or loaded from the
    #: store (its ``as_text()`` is the optimized HLO); None on the lazily
    #: jitted path
    compiled: Any = None
    source: str = "jit"


@dataclasses.dataclass
class _BatchedExecutable(_Program):
    args: Any  # (batched_pargs, catalog_token) -> the program's arguments
    target: Any  # -> (mask (B,n), cols)
    plan: R.RelNode
    out_dicts: dict  # shared with the unbatched executable's capture
    stats: dict
    bucket: int
    source: str = "jit"


@dataclasses.dataclass
class _ShardedExecutable(_Program):
    args: Any  # (batched_pargs, catalog_token) -> mesh-placed arguments
    target: Any  # -> (mask (B,n), cols)
    plan: R.RelNode
    out_dicts: dict  # shared with the unbatched executable's capture
    stats: dict
    bucket: int
    devices: int  # data-parallel shard count the bucket spreads over
    source: str = "jit"


def _run(entry: _Program, args: tuple, tier: str):
    """``entry``'s program on ``args``.  The first call of a lazily jitted
    program traces and compiles it, under a ``froid.compile`` span."""
    if entry.ran or entry.source != "jit":
        return entry.target(*args)
    with span("froid.compile", tier=tier, source="jit"):
        out = entry.target(*args)
    entry.ran = True
    return out


def _row(x, j: int):
    """Row ``j`` of a batched output."""
    return x[j]


def _shard_row(x, j: int):
    """Row ``j`` of a batched output sharded over a mesh, sliced on the one
    device that holds it: indexing the sharded array itself runs an SPMD
    program that moves the row across devices."""
    for s in x.addressable_shards:
        rows = s.index[0] if s.index else slice(None)
        lo = rows.start or 0
        if lo <= j and (rows.stop is None or j < rows.stop):
            return s.data[j - lo]
    raise IndexError(f"row {j} is on no addressable device")


@dataclasses.dataclass
class _FuseMember:
    """One member of a fused program: a (statement plan, parameter
    signature) pair stacked over its own batch bucket."""

    plan: R.RelNode
    sig: tuple
    bucket: int
    pdicts: dict  # param name -> DictEncoding | None (host metadata)
    key: tuple  # (query fingerprint, signature, bucket) — cache identity


@dataclasses.dataclass
class _FusedExecutable:
    fn: Any  # (pargs_tuple, targs_tuple, catalog_token) -> ((mask, cols), ...)
    plans: list  # member plans, fusion order
    out_dicts: list  # per-member {column -> DictEncoding | None} capture
    stats: dict  # trace stats + merge stats (shared_subtrees, cse_*, ...)
    members: list  # _FuseMember descriptors, fusion order
    merged: Any = None  # repro.fuse.merge.FusedPlan (sharing maps + explain)
    eval_counts: dict | None = None  # pool key -> trace-time evaluations


@dataclasses.dataclass
class _PoolGroup:
    """One template pool of a fused program: a parameter-unified shared
    subtree × one binding signature, evaluated once per distinct binding.
    Two members binding the same template with the same value *signature*
    land in the same group and share its distinct-binding pool — the
    cross-statement unification the CSE engine exists for."""

    fp: tuple  # canonical parametric fingerprint (template identity)
    sig: tuple  # binding signature (param_signature over hole values)
    node: R.RelNode  # canonical template subtree (holes as params)
    holes: tuple  # canonical hole parameter names, slot order
    hole_dicts: dict  # hole -> DictEncoding | None (host metadata)
    bindings: list  # [{hole: value}] distinct, slot order
    index: dict  # binding key -> slot

    def spec(self) -> "_PoolGroup":
        """Structure-only copy for the fused closure: the jitted program
        reads fp/sig/node/holes/hole_dicts; baking a wave's binding
        values (and their byte keys) into a long-lived cache entry would
        pin them for the entry's lifetime."""
        return dataclasses.replace(self, bindings=[], index={})


# ---------------------------------------------------------------------------
# Session
# ---------------------------------------------------------------------------


class Session:
    """Catalog + registry + plan/executable caches; the engine's public
    entry point.  ``prepare`` returns a :class:`PreparedStatement`;
    ``execute`` is prepare-and-run (sharing the same caches)."""

    #: bound on each cache (plans / executables / prepared handles)
    CACHE_CAP = 256

    def __init__(self, constraints: InlineConstraints | None = None,
                 cache_cap: int | None = None, store=None):
        self.catalog: dict[str, Table] = {}
        self.registry: dict[str, UdfDef] = {}
        self.constraints = constraints or InlineConstraints()
        cap = self.CACHE_CAP if cache_cap is None else cache_cap
        self._plans: _BoundedCache = _BoundedCache(cap)
        self._execs: _BoundedCache = _BoundedCache(cap)
        self._batch_execs: _BoundedCache = _BoundedCache(cap)
        self._shard_execs: _BoundedCache = _BoundedCache(cap)
        self._fuse_execs: _BoundedCache = _BoundedCache(cap)
        self._prepared: _BoundedCache = _BoundedCache(cap)
        # persistent plan tier: a repro.persist.PlanStore (or a directory
        # path — coerced here).  None = in-process caches only.  The store
        # is consulted on in-memory misses and written behind on compiles;
        # every store failure degrades to recompile (see _persist_load)
        if store is not None and not hasattr(store, "get"):
            from repro.persist.store import PlanStore

            store = PlanStore(store)
        self.store = store
        self._persist_extra = {
            "saves": 0, "save_errors": 0, "costs_loaded": 0, "costs_saved": 0,
        }
        self.cache_stats = {
            "plan_hits": 0, "plan_misses": 0,
            "exec_hits": 0, "exec_misses": 0,
            "batch_hits": 0, "batch_misses": 0,
            "shard_hits": 0, "shard_misses": 0,
            "fuse_hits": 0, "fuse_misses": 0,
            # cross-statement CSE: evaluations avoided by sharing (constant
            # refs beyond the first + template ticket-refs beyond their
            # distinct bindings), and total plan nodes covered by a shared
            # evaluation, both accumulated per fused wave
            "cse_hits": 0, "cse_shared_nodes": 0,
            # persistent tier: hits (loaded a compiled executable from the
            # store), misses (no entry), rejects (entry present but stale/
            # corrupt/unloadable — recompiled).  Monotone like every other
            # tier's counters
            "persist_hits": 0, "persist_misses": 0, "persist_rejects": 0,
        }
        # dispatched-but-unsynced AsyncResults, oldest first (backpressure)
        self._inflight: deque = deque()
        self.async_stats = {"inflight_waits": 0, "inflight_peak": 0}
        # host seconds by layer, beside the froid.* spans of the same
        # intervals: catalog loads; per device program run, its arguments
        # (executable lookup, parameter packing, catalog arrays), its
        # dispatch and its sync; per lazy result, its materialization.  A
        # call that compiles holds the compile in its args_s (AOT or store
        # load) or dispatch_s (jit); repro.telemetry counts compiles apart.
        # Per plan that prepare builds, the GroupAggs the optimizer
        # collapsed on pinned keys and the joins it gave a dense key range.
        # Per batched or fused program run, its placement: programs run
        # over a mesh and the calls they answered, and the padding rows its
        # bucket added (power-of-two and mesh)
        self.timing_stats = {
            "tables": 0, "catalog_s": 0.0, "pinned_groupaggs": 0,
            "dense_joins": 0,
            "executions": 0, "args_s": 0.0, "dispatch_s": 0.0, "sync_s": 0.0,
            "materializations": 0, "materialize_s": 0.0,
            "sharded_waves": 0, "sharded_calls": 0, "pad_calls": 0,
        }
        # lazy results materialize on their consumers' threads
        self._timing_lock = threading.Lock()
        # resilience seam: a repro.resilience.faults.FaultInjector (or any
        # object with .check(site, statements)) installed by chaos tests;
        # None in production — the seams below are no-ops then
        self.fault_injector = None
        # cost-routing seam: a repro.cost.CostRouter, created lazily the
        # first time a routed statement is prepared (None until then — the
        # sampling seams below are no-ops and unrouted sessions pay nothing)
        self.cost_router = None

    def _ensure_router(self):
        if self.cost_router is None:
            from repro.cost.router import CostRouter

            self.cost_router = CostRouter(self)
            if self.store is not None:
                self._load_costs()
        return self.cost_router

    def _load_costs(self) -> int:
        """Warm-start the router's measured cost model from the store (no-op
        on a clean miss; stale/corrupt tables degrade to an empty model)."""
        from repro.persist import costs as _costs
        from repro.persist.store import PlanCacheError

        try:
            n = _costs.load_costs(self.store, self._content_env_token(),
                                  self.cost_router)
        except PlanCacheError:
            self.cache_stats["persist_rejects"] += 1
            return 0
        if n:
            self._persist_extra["costs_loaded"] += n
        return n

    def save_costs(self) -> bool:
        """Persist the cost router's measured wave-cost EMAs so a fresh
        worker routes warm.  Fault-window samples were excluded at intake
        (``CostRouter.suppress``), so the saved table is clean by
        construction.  Returns True when a table was written."""
        if self.store is None or self.cost_router is None:
            return False
        from repro.persist import costs as _costs

        try:
            ok = _costs.save_costs(self.store, self._content_env_token(),
                                   self.cost_router)
        except Exception:
            self._persist_extra["save_errors"] += 1
            return False
        if ok:
            self._persist_extra["costs_saved"] += 1
        return ok

    @property
    def persist_stats(self) -> dict:
        """The persistent tier's view: hit/miss/reject counters, write
        counts, cost-table traffic, and the store's on-disk footprint.
        ``{"enabled": False}`` when no store is attached."""
        if self.store is None:
            return {"enabled": False}
        return {
            "enabled": True,
            "hits": self.cache_stats["persist_hits"],
            "misses": self.cache_stats["persist_misses"],
            "rejects": self.cache_stats["persist_rejects"],
            **self._persist_extra,
            "store": self.store.stats(),
        }

    @property
    def cost_stats(self) -> dict:
        """The cost router's view: counters, measured per-configuration
        wave costs (EMA), and the recent decision log.  ``{"enabled":
        False}`` until a routed statement has been prepared."""
        if self.cost_router is None:
            return {"enabled": False}
        return self.cost_router.snapshot()

    def _timed(self, **amounts) -> None:
        """Add ``amounts`` to :attr:`timing_stats`."""
        with self._timing_lock:
            for k, v in amounts.items():
                self.timing_stats[k] += v

    def _timed_materialize(self, materialize):
        """``materialize`` (a lazy result's slicing of its device outputs)
        under a ``froid.materialize`` span, counted in :attr:`timing_stats`."""
        def timed(*a):
            with span("froid.materialize"):
                t0 = time.perf_counter()
                out = materialize(*a)
                t1 = time.perf_counter()
            self._timed(materializations=1, materialize_s=t1 - t0)
            return out
        return timed

    def _fault(self, site: str, statements: tuple = ()) -> None:
        """Fault-injection seam: named executor sites call this with the
        statement fingerprints they serve; an installed injector may raise
        :class:`~repro.resilience.faults.InjectedFault` here."""
        fi = self.fault_injector
        if fi is not None:
            fi.check(site, statements)

    # -- DDL ---------------------------------------------------------------
    # name/table are positional-only so columns may be called "name"/"table"
    def create_table(self, name: str, table: Table | None = None, /, **arrays):
        with span("froid.catalog", table=name):
            t0 = time.perf_counter()
            t = table if table is not None else Table.from_arrays(**arrays)
            t.compute_stats()  # histograms for the optimizer (§Perf)
            self.catalog[name] = t
            dt = time.perf_counter() - t0
        self._timed(tables=1, catalog_s=dt)
        jax.monitoring.record_event_duration_secs(CATALOG_EVENT, dt)
        return t

    def create_function(self, udf: UdfDef):
        self.registry[udf.name] = udf
        return udf

    # -- public API --------------------------------------------------------
    def prepare(self, query, policy: ExecutionPolicy | str = FROID
                ) -> "PreparedStatement":
        policy = resolve_policy(policy)
        node = query.node if isinstance(query, Q) else query
        # the handle cache additionally keys on the batch/shard knobs (they
        # are excluded from fingerprint() so plan/executable caches still
        # share, but two prepares with different knobs must not alias —
        # the knobs live on the returned statement's policy)
        key = (plan_fingerprint(node), policy.fingerprint(),
               policy.max_batch, policy.coalesce_window_s, policy.allow_async,
               policy.max_inflight, policy.shard_batches, policy.shard_token(),
               policy.fuse, policy.max_fused_statements, policy.route)
        ps = self._prepared.get(key)
        if ps is None:
            ps = PreparedStatement(self, node, policy)
            self._prepared[key] = ps
        if policy.route:
            self._ensure_router()
        ps._ensure_plan()  # cold: bind + optimize now
        return ps

    def execute(self, query, policy: ExecutionPolicy | str = FROID,
                params: dict | None = None) -> QueryResult:
        return self.prepare(query, policy).execute(params=params)

    def execute_many(self, query, policy: ExecutionPolicy | str = FROID,
                     params_list=()) -> list[QueryResult]:
        return self.prepare(query, policy).execute_many(params_list)

    def execute_async(self, query, policy: ExecutionPolicy | str = FROID,
                      params: dict | None = None) -> "AsyncResult":
        return self.prepare(query, policy).execute_async(params=params)

    def explain(self, query, policy: ExecutionPolicy | str = FROID) -> str:
        policy = resolve_policy(policy)
        node = query.node if isinstance(query, Q) else query
        plan, _ = self._cached_plan(node, plan_fingerprint(node), policy)
        return O.explain(plan)

    # -- cache-state tokens ------------------------------------------------
    def _catalog_token(self) -> tuple:
        return tuple(
            (name, _stamp(t), t.num_rows, tuple(t.columns))
            for name, t in sorted(self.catalog.items())
        )

    def _registry_token(self) -> tuple:
        return tuple(
            (name, _stamp(u)) for name, u in sorted(self.registry.items())
        )

    def _constraints_token(self) -> tuple:
        return _norm(self.constraints)

    def _env_token(self) -> tuple:
        return (self._catalog_token(), self._registry_token(),
                self._constraints_token())

    def _content_env_token(self) -> tuple:
        """The cross-process rendering of :meth:`_env_token`: stamps (valid
        only in this process) are replaced by content digests, so two
        workers that loaded identical catalogs/registries produce identical
        persistent cache keys.  Memoized against the stamp-based token —
        the digests are recomputed only when DDL actually changed
        something, not per lookup."""
        env = self._env_token()
        cached = getattr(self, "_content_env_cache", None)
        if cached is not None and cached[0] == env:
            return cached[1]
        token = (
            tuple((name, t.num_rows, tuple(t.columns),
                   _table_content_digest(t))
                  for name, t in sorted(self.catalog.items())),
            tuple((name, _udf_content_digest(u))
                  for name, u in sorted(self.registry.items())),
            self._constraints_token(),
        )
        self._content_env_cache = (env, token)
        return token

    # -- persistent plan tier ----------------------------------------------
    def _persist_store(self, policy: ExecutionPolicy):
        """The store an executable-tier miss should consult, or None (no
        store attached / the policy opted out via ``persist=False``)."""
        s = self.store
        return s if (s is not None and policy.persist) else None

    def _persist_key(self, kind: str, query_fp, policy: ExecutionPolicy,
                     sig: tuple = (), bucket: int = 0,
                     shard_token: tuple = (), template: tuple = ()) -> tuple:
        """The five-tier cache identity as one self-describing stable tuple:
        plan fingerprint x policy fingerprint x param signature x batch
        bucket x shard token x fused/CSE template tuple, plus the content
        env token.  ``assert_stable_key`` is the enforcement point — any
        process-local value (an ``id()``, a stamp, a live object) smuggled
        into a component raises here instead of silently degrading the
        cross-worker hit rate."""
        from repro.persist.keys import assert_stable_key

        key = ("plan", kind, query_fp, policy.fingerprint(), sig, bucket,
               shard_token, template, self._content_env_token())
        assert_stable_key(key)
        return key

    def _persist_load(self, store, key: tuple):
        """``(compiled_callable, meta) | None`` — typed degradation ladder:
        version-stamp mismatch and load failures count as rejects, damaged
        entries additionally warn (:class:`~repro.persist.PlanCacheWarning`)
        and are evicted.  Every failure path returns None: the caller
        recompiles, results are never wrong and never late by more than
        one compile."""
        from repro.persist import codec
        from repro.persist.store import (
            PlanCacheCorruptError,
            PlanCacheVersionError,
            PlanCacheWarning,
        )

        try:
            got = store.get(key)
        except PlanCacheVersionError:
            self.cache_stats["persist_rejects"] += 1
            return None
        except PlanCacheCorruptError as e:
            self.cache_stats["persist_rejects"] += 1
            warnings.warn(
                f"dropping damaged persistent plan entry ({e}); recompiling",
                PlanCacheWarning, stacklevel=3)
            store.delete(key)
            return None
        if got is None:
            self.cache_stats["persist_misses"] += 1
            return None
        meta, blob = got
        try:
            loaded = codec.load_compiled(blob)
        except Exception as e:  # native deserialize: anything can surface
            self.cache_stats["persist_rejects"] += 1
            warnings.warn(
                f"persistent plan entry failed to load "
                f"({type(e).__name__}: {e}); recompiling",
                PlanCacheWarning, stacklevel=3)
            store.delete(key)
            return None
        self.cache_stats["persist_hits"] += 1
        return loaded, meta

    def _persist_save(self, store, key: tuple, compiled, *, out_dicts,
                      stats, extra: dict | None = None) -> bool:
        """Write-behind save of a freshly-compiled executable; serialization
        and store failures are counted and warned, never raised
        (persistence is an optimization, not a correctness dependency)."""
        from repro.persist import codec
        from repro.persist.store import PlanCacheWarning

        try:
            blob = codec.pack_compiled(compiled)
            meta = {
                "out_dicts": codec.encode_dicts(out_dicts),
                "stats": codec.jsonable_stats(stats),
            }
            if extra:
                meta.update(extra)
            store.put(key, meta, blob)
        except Exception as e:  # native serialize / disk: anything can surface
            self._persist_extra["save_errors"] += 1
            warnings.warn(
                f"persistent plan save failed ({type(e).__name__}: {e}); "
                f"serving from memory", PlanCacheWarning, stacklevel=3)
            return False
        self._persist_extra["saves"] += 1
        return True

    # -- planning ----------------------------------------------------------
    def _build_plan(self, node: R.RelNode, policy: ExecutionPolicy) -> R.RelNode:
        plan = node
        # the query's intended output schema (before inlining widens rows)
        try:
            wanted = R.output_columns(plan, self.catalog)
        except Exception:
            wanted = None
        if policy.inline_udfs:
            binder = Binder(self.registry, self.constraints)
            plan = binder.bind(plan)
        if policy.optimize:
            plan = O.optimize(
                plan, self.catalog, required=set(wanted) if wanted else None
            )
            self._timed(pinned_groupaggs=O.pinned_groupaggs(plan),
                        dense_joins=O.dense_joins(plan))
        if wanted is not None:
            try:
                have = R.output_columns(plan, self.catalog)
            except Exception:
                have = None
            if have is not None and have != wanted:
                plan = R.Project(plan, wanted)
        return plan

    def _cached_plan(self, node: R.RelNode, query_fp: tuple,
                     policy: ExecutionPolicy) -> tuple[R.RelNode, bool]:
        """(plan, came-from-cache).  Keyed only on the plan-relevant policy
        axes — FROID and HEKATON runs of the same inlined query share."""
        key = (query_fp, policy.inline_udfs, policy.optimize, self._env_token())
        plan = self._plans.get(key)
        if plan is not None:
            self.cache_stats["plan_hits"] += 1
            return plan, True
        self.cache_stats["plan_misses"] += 1
        plan = self._build_plan(node, policy)
        self._plans[key] = plan
        return plan, False

    # -- compiled executables ----------------------------------------------
    def _catalog_args(self, token: tuple | None = None):
        """Catalog arrays as the jit argument pytree, cached per catalog
        token — rebuilding per call would put O(tables × columns) validity
        allocations inside every warm execute.  ``token`` lets callers that
        already computed the catalog token skip recomputing it."""
        if token is None:
            token = self._catalog_token()
        cached = getattr(self, "_args_cache", None)
        if cached is not None and cached[0] == token:
            return cached[1]
        args = {
            tname: {c: (col.data, col.validity()) for c, col in t.columns.items()}
            for tname, t in self.catalog.items()
        }
        self._args_cache = (token, args)
        return args

    def _executable(self, node: R.RelNode, query_fp: tuple,
                    policy: ExecutionPolicy, params: dict | None,
                    env_token: tuple | None = None
                    ) -> tuple[_Executable, bool, bool]:
        """(executable, exec-cache-hit, plan-cache-hit)."""
        sig = param_signature(params)
        if env_token is None:
            env_token = self._env_token()
        key = (query_fp, policy.fingerprint(), env_token, sig)
        entry = self._execs.get(key)
        if entry is not None:
            self.cache_stats["exec_hits"] += 1
            return entry, True, True
        self.cache_stats["exec_misses"] += 1
        self._fault("compile", (query_fp,))
        plan, plan_hit = self._cached_plan(node, query_fp, policy)

        # iterative hook for UDF calls left in the plan (froid OFF, or
        # hybrid plans where the inlining budget ran out).  'scan' mode is
        # the only jit-traceable interpreter, so the compiled path always
        # uses it regardless of policy.udf_mode.
        has_udf_calls = any(
            isinstance(e, S.UdfCall)
            for n in R.walk_plan(plan)
            for ex in n.exprs()
            for e in S.walk(ex)
        )
        hook = None
        if has_udf_calls:
            interp = Interpreter(self.catalog, self.registry, mode="scan")
            hook = interp.eval_udf_call

        # host-side metadata (dictionaries) stays captured; data goes by
        # argument so XLA cannot constant-fold the query away — warm calls
        # measure real execution.
        meta = {
            tname: {c: col.dictionary for c, col in t.columns.items()}
            for tname, t in self.catalog.items()
        }
        pdicts = {
            name: _param_value(v).dictionary for name, v in (params or {}).items()
        }
        out_dicts: dict = {}
        trace_stats: dict = {}

        def raw(table_args, param_args):
            catalog = {
                tname: Table(
                    {
                        c: Column(data, valid, meta[tname][c])
                        for c, (data, valid) in cols.items()
                    }
                )
                for tname, cols in table_args.items()
            }
            pvals = {
                name: S.Value(data, valid, pdicts[name])
                for name, (data, valid) in param_args.items()
            }
            ex = Executor(catalog, udf_column_evaluator=hook,
                          use_pallas_agg=policy.pallas_agg)
            out = ex.execute(plan, params=pvals)
            for n, c in out.table.columns.items():
                out_dicts[n] = c.dictionary  # host metadata, set at trace
            trace_stats.update(ex.stats)
            cols = {n: (c.data, c.validity()) for n, c in out.table.columns.items()}
            return out.mask, cols

        # persistent tier: on an in-memory miss, try loading the compiled
        # executable from the store before tracing; on a store miss, AOT
        # lower+compile once (which runs the trace and fills the capture
        # dicts) and write the artifact behind.  Either way `target` below
        # is called with the same (catalog_args, pargs) pytree the jitted
        # path would see — content-env-token keying guarantees shapes match.
        from repro.persist import codec as _codec

        store = self._persist_store(policy)
        target, source = None, "jit"
        if store is not None:
            pkey = self._persist_key("exec", query_fp, policy, sig=sig)
            with span("froid.compile", tier="exec", source="store"):
                loaded = self._persist_load(store, pkey)
            if loaded is not None:
                (target, pmeta), source = loaded, "store"
                out_dicts.update(_codec.decode_dicts(pmeta.get("out_dicts"))
                                 or {})
                trace_stats.update(pmeta.get("stats") or {})
            else:
                # a compile failure raises here; only the save degrades
                pargs0 = {}
                for pname, x in (params or {}).items():
                    v = _param_value(x)
                    pargs0[pname] = (v.data, v.validity())
                with span("froid.compile", tier="exec", source="aot"):
                    target = jax.jit(raw).lower(
                        self._catalog_args(), pargs0).compile()
                source = "aot"
                self._persist_save(store, pkey, target,
                                   out_dicts=out_dicts, stats=trace_stats)
        compiled = target
        if target is None:
            target = jax.jit(raw)

        def args(param_values: dict | None = None,
                 catalog_token: tuple | None = None):
            pargs = {}
            for pname, x in (param_values or {}).items():
                v = _param_value(x)
                pargs[pname] = (v.data, v.validity())
            return self._catalog_args(catalog_token), pargs

        entry = _Executable(args, target, plan, out_dicts, trace_stats,
                            raw=raw, compiled=compiled, source=source)
        self._execs[key] = entry
        return entry, False, plan_hit

    def _batched_executable(self, node: R.RelNode, query_fp: tuple,
                            policy: ExecutionPolicy, params0: dict,
                            sig: tuple, bucket: int,
                            env_token: tuple | None = None
                            ) -> tuple[_BatchedExecutable, bool]:
        """(vmapped executable, batch-cache-hit).  The batched program is
        ``vmap`` of the unbatched raw plan closure over the parameter axis
        (catalog args broadcast), jitted once per (plan, policy, signature,
        batch bucket) — heterogeneous request streams re-specialize per
        bucket, not per distinct N."""
        if env_token is None:
            env_token = self._env_token()
        key = (query_fp, policy.fingerprint(), env_token, sig, bucket)
        entry = self._batch_execs.get(key)
        if entry is not None:
            self.cache_stats["batch_hits"] += 1
            return entry, True
        self.cache_stats["batch_misses"] += 1
        self._fault("compile", (query_fp,))
        # share the unbatched executable's raw closure and trace-time
        # capture dicts so warm execute() and execute_many() agree on
        # output dictionaries/stats regardless of which traced first
        base, _, _ = self._executable(node, query_fp, policy, params0, env_token)

        # persistent tier: the batched program persists independently of the
        # base executable (its own bucket-keyed entry).  On a store miss the
        # AOT compile traces base.raw under vmap — filling the shared
        # capture dicts exactly like the jit path would.
        store = self._persist_store(policy)
        target, source = None, "jit"
        if store is not None:
            pkey = self._persist_key("batch", query_fp, policy, sig=sig,
                                     bucket=bucket)
            with span("froid.compile", tier="batch", source="store"):
                loaded = self._persist_load(store, pkey)
            if loaded is not None:
                (target, _pmeta), source = loaded, "store"
            else:
                with span("froid.compile", tier="batch", source="aot"):
                    target = jax.jit(
                        jax.vmap(base.raw, in_axes=(None, 0))).lower(
                        self._catalog_args(),
                        _batched_avals(params0, bucket)).compile()
                source = "aot"
                self._persist_save(store, pkey, target,
                                   out_dicts=base.out_dicts, stats=base.stats)
        if target is None:
            target = jax.jit(jax.vmap(base.raw, in_axes=(None, 0)))

        def args(batched_pargs: dict, catalog_token: tuple | None = None):
            return self._catalog_args(catalog_token), batched_pargs

        entry = _BatchedExecutable(args, target, base.plan, base.out_dicts,
                                   base.stats, bucket, source=source)
        self._batch_execs[key] = entry
        return entry, False

    def _catalog_args_replicated(self, mesh, token: tuple, shard_token: tuple):
        """Catalog arg pytree broadcast to every device of ``mesh``, cached
        per (catalog token, mesh placement) — replication is a real
        cross-device transfer, so it must happen once per catalog state,
        not once per sharded dispatch.  A small LRU (not a single slot):
        statements sharded over different meshes interleave without
        re-replicating per call."""
        from repro.dist.sharding import replicated_sharding

        key = (token, shard_token)
        cache = getattr(self, "_shard_args_cache", None)
        if cache is None:
            cache = self._shard_args_cache = _BoundedCache(8)
        args = cache.get(key)
        if args is None:
            args = jax.device_put(self._catalog_args(token),
                                  replicated_sharding(mesh))
            cache[key] = args
        return args

    def _sharded_executable(self, node: R.RelNode, query_fp: tuple,
                            policy: ExecutionPolicy, params0: dict,
                            sig: tuple, bucket: int,
                            env_token: tuple | None = None
                            ) -> tuple[_ShardedExecutable, bool]:
        """(mesh-sharded executable, shard-cache-hit).  The same vmapped
        program as :meth:`_batched_executable`, but jitted with the stacked
        parameter axis sharded over the mesh's data axes
        (``repro.dist.sharding.pick_data_axes``) and the catalog replicated
        on every device.  Callers pad the bucket to a multiple of the
        data-axis product first (``_dispatch_batch``)."""
        from repro.dist.sharding import batch_sharding

        if env_token is None:
            env_token = self._env_token()
        shard_token = policy.shard_token()
        key = (query_fp, policy.fingerprint(), env_token, sig, bucket,
               shard_token)
        entry = self._shard_execs.get(key)
        if entry is not None:
            self.cache_stats["shard_hits"] += 1
            return entry, True
        self.cache_stats["shard_misses"] += 1
        self._fault("compile", (query_fp,))
        base, _, _ = self._executable(node, query_fp, policy, params0, env_token)
        mesh = policy.mesh
        parg_sharding = batch_sharding(mesh, bucket)
        if parg_sharding is None:  # callers pad; keep the invariant loud
            raise ValueError(
                f"bucket {bucket} is not divisible by the mesh data axes"
            )
        # persistent tier: the sharded program can only round-trip when its
        # input shardings are explicit (a serialized executable is
        # specialized to placements, not just avals), so the AOT path jits
        # with in_shardings = (replicated catalog, sharded param axis) —
        # exactly the placements fn below commits its inputs to.  A store
        # failure (serialization, a store reject) only skips the save; a
        # compile failure raises.
        from repro.dist.sharding import replicated_sharding

        store = self._persist_store(policy)
        target, source = None, "jit"
        if store is not None:
            pkey = self._persist_key("shard", query_fp, policy, sig=sig,
                                     bucket=bucket, shard_token=shard_token)
            with span("froid.compile", tier="shard", source="store"):
                loaded = self._persist_load(store, pkey)
            if loaded is not None:
                (target, _pmeta), source = loaded, "store"
            else:
                with span("froid.compile", tier="shard", source="aot"):
                    target = jax.jit(
                        jax.vmap(base.raw, in_axes=(None, 0)),
                        in_shardings=(replicated_sharding(mesh),
                                      parg_sharding)).lower(
                        self._catalog_args(),
                        _batched_avals(params0, bucket)).compile()
                source = "aot"
                self._persist_save(store, pkey, target,
                                   out_dicts=base.out_dicts, stats=base.stats)
        if target is None:
            # one leading-axis spec serves every stacked-param leaf
            # (trailing dims replicate); catalog args broadcast whole
            target = jax.jit(jax.vmap(base.raw, in_axes=(None, 0)))

        def args(batched_pargs: dict, catalog_token: tuple | None = None):
            cats = self._catalog_args_replicated(
                mesh, catalog_token if catalog_token is not None
                else self._catalog_token(), shard_token)
            return cats, jax.device_put(batched_pargs, parg_sharding)

        entry = _ShardedExecutable(args, target, base.plan, base.out_dicts,
                                   base.stats, bucket, policy.shard_devices(),
                                   source=source)
        self._shard_execs[key] = entry
        return entry, False

    # -- multi-statement fusion ----------------------------------------------
    def _merged_for(self, members: list, env_token: tuple):
        """The merge pass's :class:`~repro.fuse.merge.FusedPlan` for this
        member set, cached — the host consults the sharing maps on every
        wave (warm or cold) to plan template bindings, and the walk must
        not re-run per drain.

        The key includes the member plans' identities: the sharing maps
        are ``node_id``-keyed, so a plan rebuilt after a ``_plans``-cache
        eviction (same env token, fresh node ids) must get a fresh merge,
        not a stale FusedPlan whose marks match nothing.  Plan identity is
        the session stamp (monotonic, never recycled) — unlike a raw
        ``id()`` it cannot alias a dead plan's key even after eviction."""
        key = (tuple(m.key for m in members), env_token,
               tuple(_stamp(m.plan) for m in members))
        cache = getattr(self, "_merge_cache", None)
        if cache is None:
            cache = self._merge_cache = _BoundedCache(64)
        merged = cache.get(key)
        if merged is None:
            from repro.fuse.merge import merge_plans

            merged = merge_plans([m.plan for m in members])
            cache[key] = merged
        return merged

    def _fused_executable(self, members: list, policy: ExecutionPolicy,
                          shard: bool, env_token: tuple, merged,
                          groups: list, member_tmaps: list,
                          slot_names: list, template_token: tuple,
                          example_args: tuple | None = None
                          ) -> tuple[_FusedExecutable, bool]:
        """(fused executable, fuse-cache-hit).  One jitted program carrying
        every member: the merge pass's shared subtrees execute once, each
        template pool once per distinct binding, then each member's plan
        vmaps over its own stacked parameter axis (see
        ``repro.fuse.program``).  Keyed by the member tuple in canonical
        (sorted) order × policy × env token × **template identity**
        (``(fingerprint, binding signature, distinct-binding count)`` per
        pool group), so a mixed queue arriving in any order warm-hits, a
        changed distinct-binding count honestly re-specializes instead of
        hiding a retrace behind a "hit", and any DDL/catalog poke
        invalidates every member at once via the env token."""
        shard_token = policy.shard_token() if shard else ()
        # plan identity rides the key alongside the member keys: the slot
        # protocol and member_tmaps are node_id-keyed, so a plan rebuilt
        # after a _plans-cache eviction must re-specialize here too (a
        # stale entry would silently answer no template occurrence).  Plan
        # identity is the session stamp — monotonic and never recycled, so
        # unlike raw id() an evicted plan's key can never alias a live one.
        key = (tuple(m.key for m in members),
               tuple(_stamp(m.plan) for m in members), policy.fingerprint(),
               env_token, shard, shard_token, template_token)
        entry = self._fuse_execs.get(key)
        if entry is not None:
            self.cache_stats["fuse_hits"] += 1
            return entry, True
        self.cache_stats["fuse_misses"] += 1
        # persistent tier (unsharded waves): template pools gather through
        # reserved slot parameters spelled by occurrence *ordinal* (see
        # _plan_template_groups), so the fused argument pytree — dict keys
        # included — reproduces exactly in a fresh process and template
        # waves round-trip through the store like template-free ones.
        # Sharded fused programs fall back to their members' shard-tier
        # entries instead.  The persist key itself is fully stable: member
        # (fingerprint, sig, bucket) keys + the template token — no plan
        # stamps, no ids (assert_stable_key enforces this, and rejects the
        # pre-PR-10 node_id-shaped slot spellings outright).
        from repro.persist import codec as _codec

        store = self._persist_store(policy)
        persistable = (store is not None and not shard
                       and example_args is not None)
        if persistable:
            pkey = self._persist_key(
                "fused", tuple(m.key for m in members), policy,
                template=template_token)
            with span("froid.compile", tier="fused", source="store"):
                loaded = self._persist_load(store, pkey)
            if loaded is not None:
                compiled, pmeta = loaded
                out_dicts = [_codec.decode_dicts(d) or {}
                             for d in pmeta.get("out_dicts_list") or ()]
                trace_stats = dict(pmeta.get("stats") or {})

                def fn(pargs_tuple, targs_tuple,
                       catalog_token: tuple | None = None):
                    return compiled(self._catalog_args(catalog_token),
                                    pargs_tuple, targs_tuple)

                entry = _FusedExecutable(
                    fn, [m.plan for m in members], out_dicts, trace_stats,
                    members, merged, {})
                self._fuse_execs[key] = entry
                return entry, False
        self._fault("compile", tuple(m.key[0] for m in members))
        from repro.fuse.program import build_fused_raw

        raw, out_dicts, trace_stats, merged, eval_counts = build_fused_raw(
            self, members, policy, merged, [g.spec() for g in groups],
            member_tmaps, slot_names)
        jitted = jax.jit(raw)
        if persistable:
            with span("froid.compile", tier="fused", source="aot"):
                compiled = jitted.lower(self._catalog_args(),
                                        *example_args).compile()
            self._persist_save(
                store, pkey, compiled, out_dicts=None, stats=trace_stats,
                extra={"out_dicts_list":
                       [_codec.encode_dicts(d) for d in out_dicts]})
            jitted = compiled  # single compile: reuse the AOT artifact
        if shard:
            from repro.dist.sharding import batch_sharding, replicated_sharding

            mesh = policy.mesh
            # parameter-free members are unbatched: their (empty) arg
            # pytree replicates; batched members shard their stacked axis;
            # template binding stacks replicate (every member row may
            # gather any pool slot)
            shardings = tuple(
                batch_sharding(mesh, m.bucket) if m.sig
                else replicated_sharding(mesh)
                for m in members
            )

            def fn(pargs_tuple, targs_tuple,
                   catalog_token: tuple | None = None):
                cats = self._catalog_args_replicated(
                    mesh, catalog_token if catalog_token is not None
                    else self._catalog_token(), shard_token)
                placed = tuple(
                    jax.device_put(p, s) for p, s in zip(pargs_tuple, shardings)
                )
                targs = jax.device_put(targs_tuple,
                                       replicated_sharding(mesh))
                return jitted(cats, placed, targs)
        else:
            def fn(pargs_tuple, targs_tuple,
                   catalog_token: tuple | None = None):
                return jitted(self._catalog_args(catalog_token), pargs_tuple,
                              targs_tuple)

        entry = _FusedExecutable(fn, [m.plan for m in members], out_dicts,
                                 trace_stats, members, merged, eval_counts)
        self._fuse_execs[key] = entry
        return entry, False

    def execute_fused(self, calls) -> list[QueryResult]:
        """Execute a mixed-statement call list — ``[(stmt, params), ...]``
        — through as few fused device programs as fusability allows.

        Calls whose statements may share a program (same session, policy
        fingerprint and sharding placement; ``policy.fuse`` on; pure
        plans — see ``repro.fuse.analysis``) coalesce into fused programs
        of at most ``policy.max_fused_statements`` distinct statements;
        everything else (eager policies, foreign sessions, singleton
        groups) falls back to the per-statement ``execute_many`` path.

        Returns one :class:`QueryResult` per call, in input order,
        element-wise equal to the per-statement serial loop.  Fused
        results carry ``stats['fused'] / fused_statements /
        fused_programs / shared_subtrees`` — the shared-scan evidence."""
        from repro.fuse.analysis import partition_calls

        calls = [(stmt, dict(p) if p else {}) for stmt, p in calls]
        if not calls:
            return []
        results: list[QueryResult | None] = [None] * len(calls)
        groups, fallbacks = partition_calls(self, calls)
        for stmt, items in fallbacks:
            rs = stmt.execute_many([p for _, p in items])
            for (i, _), r in zip(items, rs):
                results[i] = r
        for group in groups:
            self._run_fused(group, results)
        return results  # type: ignore[return-value]

    def _run_fused(self, group: list, results: list) -> None:
        """Run one fused group — ``[(index, stmt, params), ...]`` with ≥ 2
        distinct statements and compatible policies — and scatter its
        QueryResults into ``results``."""
        env_token = self._env_token()
        policy = group[0][1].policy  # fingerprint-equal across the group
        # member = one (statement, signature) pair stacked over its tickets
        order: list[tuple] = []
        by_key: dict[tuple, dict] = {}
        for idx, stmt, params in group:
            sig = param_signature(params)
            k = (stmt._query_fp, sig)
            ent = by_key.get(k)
            if ent is None:
                ent = by_key[k] = {"stmt": stmt, "sig": sig,
                                   "idxs": [], "params": []}
                order.append(k)
            ent["idxs"].append(idx)
            ent["params"].append(params)
        # one fused wave per drain: tickets beyond the mesh-scaled batch
        # bound ride the per-statement path (already batched + pipelined).
        # max_batch is a non-identity knob, so fingerprint-equal members
        # may disagree — honor the strictest bound (and keep the cap, and
        # therefore the buckets and cache keys, arrival-order independent)
        cap = max(1, min(s.policy.max_batch for _, s, _ in group)
                  * policy.shard_devices())
        for k in order:
            ent = by_key[k]
            if len(ent["params"]) > cap:
                extra_i, extra_p = ent["idxs"][cap:], ent["params"][cap:]
                ent["idxs"], ent["params"] = ent["idxs"][:cap], ent["params"][:cap]
                for i, r in zip(extra_i, ent["stmt"].execute_many(extra_p)):
                    results[i] = r
        # canonical member order: fused cache keys are insensitive to the
        # queue's arrival order (repr: fingerprints are not comparable)
        order.sort(key=repr)
        members: list[_FuseMember] = []
        for k in order:
            ent = by_key[k]
            stmt = ent["stmt"]
            plan, _ = self._cached_plan(stmt.node, stmt._query_fp, stmt.policy)
            # parameter-free members execute once, unbatched — every ticket
            # shares the single result (mirrors execute_many's group path)
            bucket = 1 if not ent["sig"] else batch_bucket(len(ent["params"]), cap)
            pdicts = {
                name: _param_value(v).dictionary
                for name, v in ent["params"][0].items()
            }
            members.append(_FuseMember(plan, ent["sig"], bucket, pdicts,
                                       (stmt._query_fp, ent["sig"], bucket)))
        devices = policy.shard_devices()
        shard = False
        if devices > 1:
            from repro.dist.sharding import data_axis_size, pick_data_axes

            # one program, one placement: shard whenever ANY batched
            # member's bucket divides the data axes.  A non-dividing
            # batched member no longer demotes the whole program to
            # replicated — its bucket pads up to the next multiple of the
            # data-axis product (padding repeats the last ticket, exactly
            # like power-of-two bucket padding) so every batched member
            # shards under one placement.  The cap is max_batch × devices
            # — itself a multiple of the axis product — so a padded
            # bucket never exceeds it.  Only when NO batched member
            # divides (or none is batched) does the program replicate;
            # parameter-free members are unbatched and always replicate.
            batched = [m for m in members if m.sig]
            shard = any(
                pick_data_axes(policy.mesh, m.bucket) is not None
                for m in batched
            )
            if shard:
                n = data_axis_size(policy.mesh)
                for m in batched:
                    if pick_data_axes(policy.mesh, m.bucket) is None:
                        m.bucket += (-m.bucket) % n
                        m.key = (m.key[0], m.key[1], m.bucket)
        # cross-statement CSE: plan the template binding pools from the
        # wave's actual ticket values (the merge maps are cached; only the
        # binding dedup runs per wave)
        merged = self._merged_for(members, env_token)
        groups, member_tmaps, slot_maps, slot_names, template_token = \
            _plan_template_groups(merged, members,
                                  [by_key[k]["params"] for k in order])
        # ticket params stack BEFORE the executable lookup: the persistent
        # tier AOT-lowers against these exact argument pytrees on a cold
        # save.  Stacking time still counts into the wave's elapsed (t0 is
        # rewound by stack_s below); compile time still does not.
        pargs_tuple = []
        t0 = time.perf_counter()
        for m, k, smap, names in zip(members, order, slot_maps, slot_names):
            plist = by_key[k]["params"]
            if m.sig:
                padded = plist + [plist[-1]] * (m.bucket - len(plist))
                pargs = _stack_params(padded)
                for nid, slots in smap.items():
                    # each occurrence's pool-slot index rides the stacked
                    # axis as a reserved parameter (padding repeats the
                    # last ticket's slot, matching the padded params)
                    s = slots + [slots[-1]] * (m.bucket - len(slots))
                    pargs[names[nid]] = (
                        jnp.asarray(np.asarray(s, np.int32)),
                        jnp.ones((m.bucket,), bool),
                    )
                pargs_tuple.append(pargs)
            else:
                # parameter-free member: unbatched, no stacked args — but
                # const-bound template occurrences (lifted templates) still
                # gather their pool slot through the reserved parameter
                pargs = {}
                for nid, slots in smap.items():
                    pargs[names[nid]] = (
                        jnp.asarray(slots[0], jnp.int32), jnp.asarray(True))
                pargs_tuple.append(pargs)
        # binding pools pad to their d-bucket (repeat the last binding):
        # the stacked leading axis is what the fused closure specializes
        # on, so all counts in one bucket share the jitted program; padded
        # slots are evaluated and never referenced by any ticket's slot
        targs_tuple = tuple(
            _stack_params(
                g.bindings
                + [g.bindings[-1]] * (_pool_pad(len(g.bindings))
                                      - len(g.bindings)))
            for g in groups)
        stack_s = time.perf_counter() - t0
        entry, hit = self._fused_executable(
            members, policy, shard, env_token, merged, groups, member_tmaps,
            slot_names, template_token,
            example_args=(tuple(pargs_tuple), targs_tuple))
        t0 = time.perf_counter() - stack_s
        wave_fps = tuple(m.key[0] for m in members)
        self._fault("dispatch", wave_fps)
        outs = entry.fn(tuple(pargs_tuple), targs_tuple, env_token[0])
        t_dispatch = time.perf_counter() - t0
        self._fault("sync", wave_fps)
        jax.block_until_ready([mask for mask, _ in outs])
        elapsed = time.perf_counter() - t0
        n_stmts = len({m.key[0] for m in members})
        # sharing evidence: evaluations avoided this wave (constant refs
        # beyond the first evaluation + template ticket-refs beyond their
        # distinct bindings) and the covered-node total
        t_refs = sum(len(s) for smap in slot_maps for s in smap.values())
        t_evals = sum(len(g.bindings) for g in groups)
        t_slots = sum(_pool_pad(len(g.bindings)) for g in groups)
        m_stats = merged.stats
        # subtrahend is the distinct *maximal* fingerprint count — the pool
        # also holds nested entries, which are not separate evaluations the
        # per-statement path would have paid.  Template savings subtract
        # the *padded* slot count: padded pool slots are real device
        # evaluations, so counting them as avoided would overstate sharing
        self.cache_stats["cse_hits"] += (
            max(0, m_stats["shared_refs"] - m_stats["shared_maximal_subtrees"])
            + max(0, t_refs - t_slots)
        )
        self.cache_stats["cse_shared_nodes"] += m_stats["cse_shared_nodes"]
        n_tickets = sum(len(by_key[k]["idxs"]) for k in order)
        self._timed(sharded_waves=int(shard),
                    sharded_calls=n_tickets if shard else 0,
                    pad_calls=sum(m.bucket - len(by_key[k]["params"])
                                  for m, k in zip(members, order) if m.sig))
        router = self.cost_router
        if router is not None:
            router.observe_fused(
                wave_fps, elapsed, n_tickets,
                meta={"cse_bindings": t_evals, "cse_pool_slots": t_slots,
                      "cse_ticket_refs": t_refs})
        fused_explain = merged.explain()
        for j, (m, k) in enumerate(zip(members, order)):
            ent = by_key[k]
            mask, cols = outs[j]
            stats = {
                **entry.stats, "compiled": True, "batched": True,
                "fused": True, "fused_programs": 1,
                "fused_statements": n_stmts, "fused_members": len(members),
                "batch_size": len(ent["params"]), "batch_bucket": m.bucket,
                "dispatch_s": t_dispatch, "sync_s": elapsed - t_dispatch,
                # this wave's template pooling (trace-level cse_* counters
                # ride in from entry.stats via the merge pass)
                "cse_template_groups": len(groups),
                "cse_bindings": t_evals,
                "cse_pool_slots": t_slots,
                "cse_template_ticket_refs": t_refs,
                # wave-level figures (dispatch_s/sync_s/cse_*) are COPIED
                # into every ticket's result in this wave; aggregators
                # summing across results must divide by wave_tickets or
                # they double-count the wave (the router samples once at
                # the seam instead)
                "wave_tickets": n_tickets,
                "fused_explain": fused_explain,
            }
            if shard:
                stats["sharded"] = True
                stats["shard_devices"] = devices
            out_dicts = entry.out_dicts[j]

            if not m.sig:
                # unbatched member: one shared materialization serves
                # every ticket (distinct QueryResult shells, like
                # execute_many's parameter-free group)
                cell: dict = {}

                def mat_shared(mask=mask, cols=cols, out_dicts=out_dicts,
                               cell=cell):
                    if "v" not in cell:
                        cell["v"] = MaskedTable(
                            Table({n: Column(data, valid, out_dicts.get(n))
                                   for n, (data, valid) in cols.items()}),
                            mask,
                        )
                    return cell["v"]

                mat = self._timed_materialize(mat_shared)
                for i in ent["idxs"]:
                    results[i] = QueryResult(
                        None, m.plan, elapsed, dict(stats),
                        policy=ent["stmt"].policy, cache_hit=hit,
                        materialize=mat,
                    )
                continue

            def materialize(row, mask=mask, cols=cols, out_dicts=out_dicts,
                            take=_shard_row if shard else _row):
                table = Table(
                    {n: Column(take(data, row), take(valid, row),
                               out_dicts.get(n))
                     for n, (data, valid) in cols.items()}
                )
                return MaskedTable(table, take(mask, row))

            mat = self._timed_materialize(materialize)
            for row, i in enumerate(ent["idxs"]):
                results[i] = QueryResult(
                    None, m.plan, elapsed, dict(stats),
                    policy=ent["stmt"].policy, cache_hit=hit,
                    materialize=(lambda row=row, mat=mat: mat(row)),
                )

    # -- async backpressure --------------------------------------------------
    @property
    def inflight(self) -> int:
        """Dispatched-but-unsynced ``execute_async`` calls right now."""
        return len(self._inflight)

    def _admit_async(self, bound: int) -> None:
        """Make room for one more in-flight dispatch: reap already-ready
        results for free, then block on the oldest in-flight dispatch while
        the session is at the bound (the producer stalls here)."""
        dq = self._inflight
        while dq and dq[0].done():
            dq.popleft()._released = True
        while len(dq) >= max(1, bound):
            self.async_stats["inflight_waits"] += 1
            oldest = dq.popleft()
            oldest._released = True
            if oldest._marker is not None:
                jax.block_until_ready(oldest._marker)


# ---------------------------------------------------------------------------
# PreparedStatement
# ---------------------------------------------------------------------------


class PreparedStatement:
    """A query bound to a session + policy.  Calling conventions:

    * ``execute(params=…) -> QueryResult`` — the client path.  Cold call
      plans + binds (+ jits under a compiling policy); warm calls reuse the
      session caches and set ``QueryResult.cache_hit``.
    * ``stmt(params=…)`` — the raw device-level call of the compiled
      executable (mask + columns, nothing materialized); what benchmark
      timing loops invoke.
    """

    def __init__(self, session: Session, node: R.RelNode,
                 policy: ExecutionPolicy):
        self.session = session
        self.node = node
        self.policy = policy
        self._query_fp = plan_fingerprint(node)
        self._interp: Interpreter | None = None
        # stamp of the last plan this statement executed eagerly — a
        # plan-cache hit only counts as warm once *this statement* has run
        # that plan before (prepare builds the plan; the first execute is
        # still the cold half of the lifecycle)
        self._executed_plan: int | None = None

    # -- plumbing ----------------------------------------------------------
    def _ensure_plan(self) -> R.RelNode:
        plan, _ = self.session._cached_plan(self.node, self._query_fp, self.policy)
        return plan

    @property
    def plan(self) -> R.RelNode:
        return self._ensure_plan()

    def explain(self) -> str:
        return O.explain(self._ensure_plan())

    def _eager_interp(self) -> Interpreter:
        # kept across executes so the per-statement plan cache stays warm —
        # but rebuilt if the session's catalog/registry dicts were rebound
        # wholesale (benchmarks assign `db.catalog = {...}`); the identity
        # check is on live objects, so it cannot be fooled by id reuse
        interp = self._interp
        if (interp is None
                or interp.catalog is not self.session.catalog
                or interp.registry is not self.session.registry):
            interp = self._interp = Interpreter(
                self.session.catalog, self.session.registry,
                mode=self.policy.udf_mode,
                jit_statements=self.policy.jit_statements,
            )
        return interp

    # -- cost routing ------------------------------------------------------
    def _route_target(self) -> "PreparedStatement":
        """The statement the cost router currently picks for this routed
        statement — ``self`` when the incumbent policy wins, else a
        delegate prepared under the chosen policy.  The delegate's policy
        has ``route=False`` (one routing decision per call, never a
        chain), but its samples still train the router — it is the
        session's router, keyed by policy fingerprint."""
        router = self.session._ensure_router()
        pol = router.choose_policy(self)
        if pol.fingerprint() == self.policy.fingerprint():
            return self
        return self.session.prepare(self.node, pol.routed(False))

    # -- execution ---------------------------------------------------------
    def __call__(self, params: dict | None = None):
        """Raw call: device outputs only (see class docstring)."""
        if not self.policy.compile_plan:
            return self.execute(params=params).masked.mask
        env_token = self.session._env_token()
        entry, _, _ = self.session._executable(
            self.node, self._query_fp, self.policy, params, env_token
        )
        return _run(entry, entry.args(params, env_token[0]), "exec")

    def execute(self, params: dict | None = None) -> QueryResult:
        if self.policy.route and self.policy.compile_plan:
            target = self._route_target()
            if target is not self:
                return target.execute(params=params)
        if self.policy.compile_plan:
            return self._execute_compiled(params)
        return self._execute_eager(params)

    # -- batched execution -------------------------------------------------
    def execute_many(self, params_list) -> list[QueryResult]:
        """Execute once per parameter set, set-oriented: same-signature
        sets are stacked into one device program (``vmap`` over the param
        axis; tables broadcast) instead of N dispatch+sync round trips.
        Mixed-signature lists split into per-signature sub-batches; batches
        larger than ``policy.max_batch`` split into chunks.  Returns one
        :class:`QueryResult` per input, in input order, element-wise equal
        to the serial ``execute`` loop.

        A policy carrying a mesh (``policy.sharded(mesh)``) shards the
        stacked parameter axis over the mesh's data axes: ``max_batch``
        bounds the *per-device* batch, so one mesh dispatch carries up to
        ``max_batch × shard_devices()`` parameter sets.  Every bucket runs
        on the whole mesh: one the data axes don't divide (small
        remainders, tiny batches) pads up to the next multiple of their
        product by repeating its last parameter set, and each result is
        sliced from the device shard that holds its row.

        Chunked dispatches are **pipelined**: every chunk is dispatched
        before any chunk syncs (bounded by ``policy.max_inflight`` unsynced
        dispatches — past the bound a new dispatch first syncs the oldest),
        then one barrier at the end collects them all, so host-side
        stacking of chunk i+1 overlaps device compute of chunk i.
        ``stats['pipelined_chunks']`` reports how many chunks the call
        dispatched before that barrier.

        Results materialize lazily from the shared device batch, so an
        unmaterialized result keeps its whole bucket's outputs alive —
        callers holding results long-term should touch ``masked`` (or
        ``table``) to shrink retention to their own rows."""
        params_list = [dict(p) if p else {} for p in params_list]
        if not params_list:
            return []
        if self.policy.route and self.policy.compile_plan:
            target = self._route_target()
            if target is not self:
                return target.execute_many(params_list)
        if not self.policy.compile_plan:
            # eager policies have no device program to batch; stay serial
            return [self.execute(params=p) for p in params_list]
        with span("froid.execute"):
            return self._execute_many_compiled(params_list)

    def _execute_many_compiled(self, params_list: list[dict]
                               ) -> list[QueryResult]:
        env_token = self.session._env_token()
        groups: dict[tuple, list[int]] = {}
        for i, p in enumerate(params_list):
            groups.setdefault(param_signature(p), []).append(i)
        results: list[QueryResult | None] = [None] * len(params_list)
        pending: list[dict] = []  # dispatched-but-unsynced chunk records
        for sig, idxs in groups.items():
            if not sig:
                # parameter-free: every invocation is the same program run —
                # one execution serves the whole group, surfaced as distinct
                # QueryResult shells (per-result stats stay independent)
                r = self._execute_compiled(None)
                for i in idxs:
                    results[i] = QueryResult(
                        r.masked, r.plan, r.elapsed_s, dict(r.stats),
                        policy=r.policy, cache_hit=r.cache_hit,
                    )
                continue
            # mesh capacity: max_batch bounds the per-device batch
            cap = max(1, self.policy.max_batch * self.policy.shard_devices())
            for s in range(0, len(idxs), cap):
                chunk = idxs[s:s + cap]
                self._dispatch_batch(chunk, [params_list[i] for i in chunk],
                                     sig, env_token, pending, cap)
        # the barrier: all chunks are in flight; sync in dispatch order
        npend = len(pending)
        for rec in pending:
            self._finalize_batch(rec, results, npend)
        return results  # type: ignore[return-value]

    def _dispatch_batch(self, idxs: list[int], plist: list[dict], sig: tuple,
                        env_token: tuple, pending: list, cap: int) -> None:
        """Dispatch one chunk (no sync) and append its record to
        ``pending`` for the caller's end-of-call barrier."""
        k = len(plist)
        bucket = batch_bucket(k, cap)
        devices = self.policy.shard_devices()
        shard = devices > 1
        router = self.session.cost_router
        if router is not None and self.policy.route:
            # bucket routing: ride an already-measured larger bucket when
            # that beats cold-compiling the natural one (bucket ≥ k always
            # holds — rides only go up, and padding repeats the last set)
            bucket = router.choose_bucket(self, sig, k, bucket, cap,
                                          shard=shard)
        if shard:
            # every wave uses the mesh: a bucket the data axes don't divide
            # pads up to the next multiple of their product, as the fused
            # path pads a non-dividing member.  The cap is max_batch ×
            # devices, itself such a multiple, so the per-device batch
            # stays within max_batch
            bucket += (-bucket) % devices
        # runahead bound: past max_inflight unsynced chunks, sync the
        # oldest before issuing another dispatch (same backpressure rule
        # as execute_async — the host cannot queue unbounded device work)
        bound = max(1, self.policy.max_inflight)
        unsynced = [r for r in pending if not r["synced"]]
        while len(unsynced) >= bound:
            self._sync_chunk(unsynced.pop(0))
        sess = self.session
        t0 = time.perf_counter()
        with span("froid.args"):
            lookup = (sess._sharded_executable if shard
                      else sess._batched_executable)
            entry, hit = lookup(self.node, self._query_fp, self.policy,
                                plist[0], sig, bucket, env_token)
            t_ready = time.perf_counter()
            # pad to the bucket by repeating the last param set; padding
            # rows are computed and discarded (never surfaced in results)
            padded = plist + [plist[-1]] * (bucket - k)
            args = entry.args(_stack_params(padded, host=shard), env_token[0])
        t1 = time.perf_counter()
        with span("froid.dispatch", devices=devices):
            sess._fault("dispatch", (self._query_fp,))
            mask, cols = _run(entry, args, "shard" if shard else "batch")
        t2 = time.perf_counter()
        sess._timed(executions=1, args_s=t1 - t0, dispatch_s=t2 - t1,
                    sharded_waves=int(shard), sharded_calls=k if shard else 0,
                    pad_calls=bucket - k)
        pending.append({
            "idxs": idxs, "entry": entry, "hit": hit, "mask": mask,
            "cols": cols, "k": k, "bucket": bucket, "shard": shard,
            "devices": devices, "t0": t_ready, "dispatch_s": t2 - t_ready,
            "synced": False, "sig": sig,
        })

    def _sync_chunk(self, rec: dict) -> float:
        """Wait for a dispatched chunk's outputs; returns the clock after."""
        t0 = time.perf_counter()
        with span("froid.sync"):
            jax.block_until_ready(rec["mask"])
        t1 = time.perf_counter()
        rec["synced"] = True
        self.session._timed(sync_s=t1 - t0)
        return t1

    def _finalize_batch(self, rec: dict, results: list,
                        pipelined: int) -> None:
        """Sync one dispatched chunk and build its QueryResults.
        ``sync_s`` is the wait from dispatch end to this chunk's barrier
        arrival — under pipelining that wait overlaps the later chunks'
        host-side stacking, which is the point; the session's
        ``timing_stats['sync_s']`` counts the wait itself."""
        entry, mask, cols = rec["entry"], rec["mask"], rec["cols"]
        self.session._fault("sync", (self._query_fp,))
        elapsed = self._sync_chunk(rec) - rec["t0"]
        stats = {
            **entry.stats, "compiled": True, "batched": True,
            "batch_size": rec["k"], "batch_bucket": rec["bucket"],
            "dispatch_s": rec["dispatch_s"],
            "sync_s": elapsed - rec["dispatch_s"],
            "pipelined_chunks": pipelined,
            # chunk-level timings are copied into every ticket's result in
            # this chunk; aggregators summing across results must divide
            # by wave_tickets or they double-count the chunk
            "wave_tickets": rec["k"],
        }
        if rec["shard"]:
            stats["sharded"] = True
            stats["shard_devices"] = rec["devices"]
        router = self.session.cost_router
        if router is not None:
            router.observe_many(self._query_fp, self.policy, rec["sig"],
                                rec["bucket"], elapsed, rec["k"],
                                shard=rec["shard"])

        row = _shard_row if rec["shard"] else _row

        @self.session._timed_materialize
        def materialize(j: int) -> MaskedTable:
            table = Table(
                {n: Column(row(data, j), row(valid, j),
                           entry.out_dicts.get(n))
                 for n, (data, valid) in cols.items()}
            )
            return MaskedTable(table, row(mask, j))

        for j, i in enumerate(rec["idxs"]):
            results[i] = QueryResult(
                None, entry.plan, elapsed, dict(stats), policy=self.policy,
                cache_hit=rec["hit"],
                materialize=(lambda j=j: materialize(j)),
            )

    # -- async execution ---------------------------------------------------
    def execute_async(self, params: dict | None = None) -> AsyncResult:
        """Dispatch without waiting: the device call is issued and a future
        returned immediately; ``block_until_ready`` is deferred to result
        access, so callers pipeline host work (or further dispatches)
        against device compute.  Policies with ``allow_async=False`` (or no
        compiled plan) degrade to synchronous execution behind the same
        interface.

        In-flight dispatches are bounded per session by
        ``policy.max_inflight``: at the bound, a new dispatch first blocks
        on the oldest unsynced one (and ``AsyncResult.result()`` releases
        its slot), so a producer outrunning the device stalls instead of
        queueing unbounded work."""
        if self.policy.route and self.policy.compile_plan:
            target = self._route_target()
            if target is not self:
                return target.execute_async(params=params)
        if not (self.policy.compile_plan and self.policy.allow_async):
            return AsyncResult(self.execute(params=params))
        sess = self.session
        sess._admit_async(self.policy.max_inflight)
        with span("froid.execute"):
            t0 = time.perf_counter()
            with span("froid.args"):
                env_token = sess._env_token()
                entry, exec_hit, plan_hit = sess._executable(
                    self.node, self._query_fp, self.policy, params, env_token
                )
                t_ready = time.perf_counter()
                args = entry.args(params, env_token[0])
            t1 = time.perf_counter()
            with span("froid.dispatch"):
                sess._fault("dispatch", (self._query_fp,))
                mask, cols = _run(entry, args, "exec")
            t2 = time.perf_counter()
        sess._timed(executions=1, args_s=t1 - t0, dispatch_s=t2 - t1)
        dispatch_s = t2 - t_ready
        stats = {**entry.stats, "compiled": True, "async": True,
                 "dispatch_s": dispatch_s}
        result: QueryResult

        def materialize() -> MaskedTable:
            with span("froid.materialize"):
                t3 = time.perf_counter()
                with span("froid.sync"):
                    jax.block_until_ready(mask)
                t4 = time.perf_counter()
                table = Table(
                    {n: Column(data, valid, entry.out_dicts.get(n))
                     for n, (data, valid) in cols.items()}
                )
                t5 = time.perf_counter()
            sess._timed(sync_s=t4 - t3, materializations=1,
                        materialize_s=t5 - t4)
            result.stats["sync_s"] = t4 - t3
            result.elapsed_s = dispatch_s + t4 - t3
            return MaskedTable(table, mask)

        result = QueryResult(None, entry.plan, dispatch_s, stats,
                             policy=self.policy,
                             cache_hit=exec_hit and plan_hit,
                             materialize=materialize)
        ar = AsyncResult(result, marker=mask, session=sess)
        sess._inflight.append(ar)
        sess.async_stats["inflight_peak"] = max(
            sess.async_stats["inflight_peak"], len(sess._inflight),
        )
        return ar

    def _execute_compiled(self, params) -> QueryResult:
        sess = self.session
        with span("froid.execute"):
            t0 = time.perf_counter()
            with span("froid.args"):
                env_token = sess._env_token()
                entry, exec_hit, plan_hit = sess._executable(
                    self.node, self._query_fp, self.policy, params, env_token
                )
                t_ready = time.perf_counter()
                args = entry.args(params, env_token[0])
            t1 = time.perf_counter()
            with span("froid.dispatch"):
                sess._fault("dispatch", (self._query_fp,))
                mask, cols = _run(entry, args, "exec")
            t2 = time.perf_counter()
            with span("froid.sync"):
                sess._fault("sync", (self._query_fp,))
                jax.block_until_ready(mask)
            t3 = time.perf_counter()
        sess._timed(executions=1, args_s=t1 - t0, dispatch_s=t2 - t1,
                    sync_s=t3 - t2)
        # from the executable in hand to the outputs ready, as the batched
        # and async paths count it
        elapsed = t3 - t_ready
        router = sess.cost_router
        if router is not None:
            router.observe_serial(self._query_fp, self.policy, elapsed)
        table = Table(
            {n: Column(data, valid, entry.out_dicts.get(n))
             for n, (data, valid) in cols.items()}
        )
        masked = MaskedTable(table, mask)
        stats = {**entry.stats, "compiled": True,
                 "dispatch_s": t2 - t_ready, "sync_s": t3 - t2}
        return QueryResult(masked, entry.plan, elapsed, stats,
                           policy=self.policy,
                           cache_hit=exec_hit and plan_hit)

    def _execute_eager(self, params) -> QueryResult:
        plan, plan_hit = self.session._cached_plan(
            self.node, self._query_fp, self.policy
        )
        warm = plan_hit and self._executed_plan == _stamp(plan)
        self._executed_plan = _stamp(plan)
        interp = self._eager_interp()
        executor = Executor(
            self.session.catalog,
            udf_column_evaluator=interp.eval_udf_call,
            use_pallas_agg=self.policy.pallas_agg,
        )
        pvals = {n: _param_value(v) for n, v in (params or {}).items()}
        before = dict(interp.stats)
        t0 = time.perf_counter()
        self.session._fault("interp", (self._query_fp,))
        masked = executor.execute(plan, params=pvals)
        jax.block_until_ready(masked.mask)
        elapsed = time.perf_counter() - t0
        # interpreter stats are cumulative over the statement's lifetime;
        # report this execution's delta
        delta = {k: interp.stats[k] - before.get(k, 0) for k in interp.stats}
        stats = {**executor.stats, **delta}
        return QueryResult(masked, plan, elapsed, stats,
                           policy=self.policy, cache_hit=warm)
