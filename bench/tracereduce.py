"""The reduction from a profiler trace to per-layer numbers.

A trace is read into two lists of intervals, in seconds on one clock:
device operations ``(name, start, end, opcode)`` from the ``XLA Ops``
line of each TPU plane, named by their HLO instruction's text, and host
spans ``(label, start, end)`` that the benchmark wrapped around its calls
into the engine (``bench.*`` annotations; the label adds the statement
name where one was given).
Everything below works on those lists, so a test can feed it a
synthesized trace.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

#: the host span that covers the measured window; its extent is the
#: traced window
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Op:
    name: str
    start: float
    end: float
    kind: str = ""      # the HLO opcode
    device: int = 0


@dataclasses.dataclass
class Span:
    label: str
    start: float
    end: float


def merge(intervals) -> list[tuple[float, float]]:
    """The union of ``(start, end)`` intervals as sorted disjoint ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def busy_seconds(ops, lo: float, hi: float,
                 n_devices: int | None = None) -> float:
    """Seconds of ``[lo, hi]`` in which some of ``ops`` ran on a device,
    overlaps counted once, averaged over ``n_devices`` (by default the
    devices that ran any of them)."""
    devices = sorted({o.device for o in ops})
    if not devices:
        return 0.0
    total = 0.0
    for d in devices:
        merged = merge(clip([(o.start, o.end) for o in ops if o.device == d],
                            lo, hi))
        total += sum(e - s for s, e in merged)
    return total / (n_devices or len(devices))


def gaps(ops, lo: float, hi: float) -> list[tuple[float, float]]:
    """Intervals of ``[lo, hi]`` in which no operation ran on any device."""
    merged = merge(clip([(o.start, o.end) for o in ops], lo, hi))
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def gaps_by_host(ops, spans, lo: float, hi: float) -> dict[str, float]:
    """Idle device seconds, summed by what the host was doing: the
    innermost span covering each gap's middle (``idle`` where none does).
    The spans come from one host thread, so they nest."""
    order = sorted((sp for sp in spans if sp.label != WINDOW_SPAN),
                   key=lambda sp: (sp.start, -sp.end))
    out: dict[str, float] = {}
    stack: list[Span] = []
    i = 0
    for t, dur in sorted(((s + e) / 2, e - s) for s, e in gaps(ops, lo, hi)):
        while i < len(order) and order[i].start <= t:
            stack.append(order[i])
            i += 1
        while stack and stack[-1].end <= t:
            stack.pop()
        label = stack[-1].label if stack else "idle"
        out[label] = out.get(label, 0.0) + dur
    return out


def op_seconds(ops, lo: float, hi: float) -> dict[str, float]:
    """Device seconds per operation name inside ``[lo, hi]``, averaged
    over the devices that ran any."""
    devices = {o.device for o in ops} or {0}
    out: dict[str, float] = {}
    for o in ops:
        d = min(o.end, hi) - max(o.start, lo)
        if d > 0:
            key = short_name(o.name)
            out[key] = out.get(key, 0.0) + d / len(devices)
    return out


def short_name(hlo: str) -> str:
    """An ``XLA Ops`` event is named by its HLO instruction's text; this is
    that text cut to its first 160 characters (name, shapes, opcode)."""
    return hlo[:160]


def opcode(hlo: str) -> str:
    """The opcode of an HLO instruction's text (``fusion``, ``sort``,
    ``while``, ...), or ``""``."""
    m = _OPCODE.search(hlo.split(" = ", 1)[-1])
    return m.group(1) if m else ""


_OPCODE = re.compile(r"(?:^|[\s)}\]])([a-z][a-z0-9-]*)\(")


def is_sort(op: Op) -> bool:
    return op.kind == "sort"


def top(d: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


@dataclasses.dataclass
class Summary:
    """What the metric readers take from one traced window."""

    window_s: float
    busy_s: float
    sort_s: float
    op_s: dict
    idle_by_host: dict

    def idle_pct(self) -> float | None:
        """Share in percent of the window in which no operation ran."""
        if self.window_s <= 0:
            return None
        return (1.0 - self.busy_s / self.window_s) * 100.0

    def breakdown(self) -> dict:
        return {"device_ops": top(self.op_s),
                "idle_gaps": top(self.idle_by_host)}


def summarize(ops, spans) -> Summary:
    window = [sp for sp in spans if sp.label == WINDOW_SPAN]
    if len(window) != 1:
        raise ValueError(f"the trace holds {len(window)} {WINDOW_SPAN} spans")
    lo, hi = window[0].start, window[0].end
    return Summary(window_s=hi - lo, busy_s=busy_seconds(ops, lo, hi),
                   sort_s=busy_seconds([o for o in ops if is_sort(o)], lo, hi,
                                       len({o.device for o in ops})),
                   op_s=op_seconds(ops, lo, hi),
                   idle_by_host=gaps_by_host(ops, spans, lo, hi))


def _stat(event, key: str) -> str:
    for k, v in event.stats:
        if k == key:
            return str(v)
    return ""


def read(trace_dir: str) -> tuple[list[Op], list[Span]]:
    """Device operations and ``bench.*`` host spans of the newest trace
    under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    prof = ProfileData.from_file(files[-1])
    ops, spans = [], []
    for plane in prof.planes:
        if plane.name.startswith("/device:TPU:"):
            device = int(plane.name.rsplit(":", 1)[1])
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    s = e.start_ns * 1e-9
                    ops.append(Op(e.name, s, s + e.duration_ns * 1e-9,
                                  opcode(e.name), device))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if not e.name.startswith("bench."):
                        continue
                    stmt = _stat(e, "stmt")
                    s = e.start_ns * 1e-9
                    spans.append(Span(e.name + (f":{stmt}" if stmt else ""),
                                      s, s + e.duration_ns * 1e-9))
    return ops, spans
