"""The engine's side of a profiler trace, read beside
:mod:`bench.tracereduce` and on its clock.

The engine records ``froid.*`` host spans at its layer boundaries (see
``repro.telemetry``) on the host thread that does the work, and runs each
plan operator under a ``froid.<family>`` ``jax.named_scope``, which ends
up in the op-name metadata of the compiled program's instructions.  A
TPU ``XLA Ops`` event carries only its instruction's text, so the op
name comes from the optimized HLO that the trace keeps for each module
on its ``/host:metadata`` plane, found by the ``XLA Modules`` event the
op runs in.  This module reads both: each device op with its operator
family, and each ``froid.*`` and ``bench.*`` span with its host thread.
From them it gives device seconds per operator family, engine seconds
per span name, and the device's idle gaps put down to what the host
threads were doing.

    python3 -m bench.enginetrace [trace_dir]

prints that breakdown for the newest trace under ``trace_dir`` (by
default the one the last ``--trace 1`` run left).  A trace of a program
without the engine's spans and scopes reads as having none of them.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import os
import re
import sys

from bench import tracereduce as T

#: the label of device time under no ``froid.*`` scope
UNSCOPED = "none"
_FAMILY = re.compile(r"froid\.[a-z_]+")


@dataclasses.dataclass
class ScopedOp(T.Op):
    scope: str = UNSCOPED   # the op's operator family


@dataclasses.dataclass
class ThreadSpan(T.Span):
    thread: int = 0         # the span's line on the ``/host:CPU`` plane


def family(op_path: str) -> str:
    """The operator family of an op-name path: its innermost
    ``froid.<family>`` component (a child operator's scope nests inside
    its parent's; ``vmap(froid.join)`` counts as ``froid.join``)."""
    found = _FAMILY.findall(op_path)
    return found[-1] if found else UNSCOPED


def scope_seconds(ops, lo: float, hi: float) -> dict[str, float]:
    """Device seconds in ``[lo, hi]`` per operator family, each as the
    union of its ops' intervals (a ``while`` and its body's fusions count
    once), averaged over the devices that ran any op."""
    n = len({o.device for o in ops})
    return {s: T.busy_seconds([o for o in ops if o.scope == s], lo, hi, n)
            for s in sorted({o.scope for o in ops})}


def _innermost(spans, times) -> list:
    """For each of the ascending ``times``, the innermost of ``spans``
    (nested, one thread's) that covers it, or None."""
    order = sorted(spans, key=lambda sp: (sp.start, -sp.end))
    out, stack, i = [], [], 0
    for t in times:
        while i < len(order) and order[i].start <= t:
            stack.append(order[i])
            i += 1
        while stack and stack[-1].end <= t:
            stack.pop()
        out.append(stack[-1] if stack else None)
    return out


def gaps_by_host(ops, spans, lo: float, hi: float) -> dict[str, float]:
    """Idle device seconds by what the host was doing: for each gap, the
    innermost span covering its middle on each host thread; of those a
    ``froid.*`` span before a ``bench.*`` one, then the one that started
    later; ``idle`` where no thread has one.  With one thread and only
    ``bench.*`` spans this is :func:`bench.tracereduce.gaps_by_host`."""
    gaps = sorted(((s + e) / 2, e - s) for s, e in T.gaps(ops, lo, hi))
    times = [t for t, _ in gaps]
    by_thread: dict[int, list] = {}
    for sp in spans:
        if sp.label != T.WINDOW_SPAN:
            by_thread.setdefault(sp.thread, []).append(sp)
    covering = [_innermost(sps, times) for sps in by_thread.values()]
    out: dict[str, float] = {}
    for k, (_, dur) in enumerate(gaps):
        cands = [c[k] for c in covering if c[k] is not None]
        best = max(cands, key=lambda sp: (sp.label.startswith("froid."),
                                          sp.start), default=None)
        label = best.label if best is not None else "idle"
        out[label] = out.get(label, 0.0) + dur
    return out


def span_seconds(spans, lo: float, hi: float) -> dict[str, list]:
    """``{name: [count, seconds]}`` of the ``froid.*`` spans that start in
    ``[lo, hi]``."""
    out: dict[str, list] = {}
    for sp in spans:
        if sp.label.startswith("froid.") and lo <= sp.start <= hi:
            c = out.setdefault(sp.label, [0, 0.0])
            c[0] += 1
            c[1] += sp.end - sp.start
    return out


@dataclasses.dataclass
class Summary:
    """What the engine's metric readers take from one traced window."""

    window_s: float
    busy_s: float
    scope_s: dict       # operator family -> device seconds
    span_s: dict        # froid.* span name -> [count, seconds]
    idle_by_host: dict  # label -> idle device seconds

    def scoped(self) -> bool:
        """Whether any device op ran under a ``froid.*`` scope."""
        return any(s != UNSCOPED for s in self.scope_s)

    def share(self, scope: str) -> float | None:
        """``scope``'s device seconds in percent of the busy time, or None
        where no op carries a scope."""
        if not self.scoped() or self.busy_s <= 0:
            return None
        return self.scope_s.get(scope, 0.0) / self.busy_s * 100.0

    def mean_ms(self, names, per: str) -> float | None:
        """Seconds of the spans ``names`` per ``per`` span, in ms, or None
        where there is no ``per`` span."""
        n = self.span_s.get(per, [0, 0.0])[0]
        if not n:
            return None
        return sum(self.span_s.get(x, [0, 0.0])[1] for x in names) / n * 1e3

    def breakdown(self) -> dict:
        return {"device_scopes": T.top(self.scope_s),
                "idle_gaps": T.top(self.idle_by_host),
                "engine_spans": sorted(([k, n, s] for k, (n, s)
                                        in self.span_s.items()),
                                       key=lambda r: -r[2])}


def summarize(ops, spans) -> Summary:
    window = [sp for sp in spans if sp.label == T.WINDOW_SPAN]
    if len(window) != 1:
        raise ValueError(f"the trace holds {len(window)} {T.WINDOW_SPAN} "
                         f"spans")
    lo, hi = window[0].start, window[0].end
    return Summary(window_s=hi - lo, busy_s=T.busy_seconds(ops, lo, hi),
                   scope_s=scope_seconds(ops, lo, hi),
                   span_s=span_seconds(spans, lo, hi),
                   idle_by_host=gaps_by_host(ops, spans, lo, hi))


def newest(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    return files[-1]


# -- the trace file's HLO: a protobuf reader for the few fields it needs
def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, span: tuple[int, int]):
    """``(field number, value)`` of the message in ``buf[span]``: an int
    for a varint, ``(start, end)`` for a length-delimited field."""
    i, end = span
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif kind in (1, 5):
            v, i = None, i + (8 if kind == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, v


def _sub(buf, span, field: int) -> list:
    return [v for f, v in _fields(buf, span) if f == field]


def _text(buf, spans) -> str:
    return "".join(bytes(buf[a:b]).decode() for a, b in spans)


def op_paths(path: str) -> dict[str, dict[str, str]]:
    """``{module: {instruction: op-name path}}`` from the optimized HLO
    the trace file ``path`` keeps per module (XSpace.planes ›
    ``/host:metadata`` › event_metadata › the ``Hlo Proto`` stat ›
    HloProto.hlo_module › computations › instructions › metadata.op_name).
    """
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out: dict[str, dict[str, str]] = {}
    for plane in _sub(buf, (0, len(buf)), 1):
        if _text(buf, _sub(buf, plane, 2)) != "/host:metadata":
            continue
        proto_stat = {_sub(buf, m, 1)[0] for e in _sub(buf, plane, 5)
                      for m in _sub(buf, e, 2)
                      if _text(buf, _sub(buf, m, 2)) == "Hlo Proto"}
        for entry in _sub(buf, plane, 4):
            for meta in _sub(buf, entry, 2):
                ops = out.setdefault(_text(buf, _sub(buf, meta, 2)), {})
                for stat in _sub(buf, meta, 5):
                    if not proto_stat & set(_sub(buf, stat, 1)):
                        continue
                    for proto in _sub(buf, stat, 6):
                        for module in _sub(buf, proto, 1):
                            for comp in _sub(buf, module, 3):
                                for ins in _sub(buf, comp, 2):
                                    name = op = ""
                                    for f, v in _fields(buf, ins):
                                        if f == 1:
                                            name = _text(buf, [v])
                                        elif f == 7:
                                            op = _text(buf, _sub(buf, v, 2))
                                    ops[name] = op
    return out


def instruction(op_text: str) -> str:
    """The instruction name of an ``XLA Ops`` event's HLO text."""
    return op_text.split(" = ", 1)[0].lstrip("%")


def read(path: str) -> tuple[list[ScopedOp], list[ThreadSpan]]:
    """Device ops with their operator family, and ``bench.*`` and
    ``froid.*`` host spans with their thread, of the trace file ``path``."""
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(path)
    paths = op_paths(path)
    ops, spans = [], []
    for plane in prof.planes:
        if plane.name.startswith("/device:TPU:"):
            device = int(plane.name.rsplit(":", 1)[1])
            lines = {line.name: list(line.events) for line in plane.lines}
            modules = sorted((e.start_ns, e.end_ns, e.name)
                             for e in lines.get("XLA Modules", ()))
            starts = [m[0] for m in modules]
            for e in lines.get("XLA Ops", ()):
                k = bisect.bisect_right(starts, e.start_ns) - 1
                in_module = paths.get(modules[k][2], {}) \
                    if k >= 0 and e.start_ns < modules[k][1] else {}
                s = e.start_ns * 1e-9
                ops.append(ScopedOp(
                    e.name, s, s + e.duration_ns * 1e-9, T.opcode(e.name),
                    device, family(in_module.get(instruction(e.name), ""))))
        elif plane.name == "/host:CPU":
            for thread, line in enumerate(plane.lines):
                for e in line.events:
                    if not e.name.startswith(("bench.", "froid.")):
                        continue
                    stmt = T._stat(e, "stmt") if e.name.startswith("bench.") \
                        else ""
                    s = e.start_ns * 1e-9
                    spans.append(ThreadSpan(
                        e.name + (f":{stmt}" if stmt else ""), s,
                        s + e.duration_ns * 1e-9, thread))
    return ops, spans


_cache: dict = {}


def load(trace_dir: str) -> Summary:
    """The :class:`Summary` of the newest trace under ``trace_dir``, read
    once per trace file."""
    path = newest(trace_dir)
    key = (path, os.path.getmtime(path))
    if key not in _cache:
        _cache.clear()
        _cache[key] = summarize(*read(path))
    return _cache[key]


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if args:
        trace_dir = args[0]
    else:
        from bench.harness import TRACE_DIR
        trace_dir = str(TRACE_DIR)
    s = load(trace_dir)
    print(json.dumps({"window_s": s.window_s, "busy_s": s.busy_s,
                      **s.breakdown()}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
