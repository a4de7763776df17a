"""Roofline term extraction from compiled dry-run artifacts.

    compute term    = HLO_FLOPs / (chips × peak_FLOP/s)
    memory term     = HLO_bytes / (chips × HBM_bw)
    collective term = collective_bytes / (chips × link_bw)

Sources: ``compiled.cost_analysis()`` for FLOPs/bytes; collective bytes are
parsed out of the optimized HLO text (operand sizes of all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute).

Hardware model (TPU v5e target): 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI.
"""
from __future__ import annotations

import dataclasses
import re

PEAK_FLOPS = 197e12  # bf16 per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1,
    "pred": 1, "c64": 8, "c128": 16,
}

_TYPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.+?)\s+([\w\-]+)\(")
_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)


def _type_bytes(type_str: str) -> int:
    """Bytes of an HLO type string (handles tuples)."""
    total = 0
    for dt, dims in _TYPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class CollectiveStats:
    bytes_by_kind: dict
    count_by_kind: dict

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_kind.values())


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Sum operand sizes of every collective op in optimized HLO."""
    sizes: dict[str, int] = {}
    # pass 1: def-site sizes
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if m:
            name, type_str, _ = m.groups()
            sizes[name] = _type_bytes(type_str)
    bytes_by = {k: 0 for k in _COLLECTIVES}
    count_by = {k: 0 for k in _COLLECTIVES}
    # pass 2: collective call sites; operands are %names inside parens
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if not m:
            continue
        name, type_str, op = m.groups()
        kind = next((k for k in _COLLECTIVES if op.startswith(k)), None)
        if kind is None:
            continue
        count_by[kind] += 1
        args = re.search(r"\((.*)\)", line)
        operand_bytes = 0
        if args:
            for ref in re.findall(r"%([\w.\-]+)", args.group(1)):
                operand_bytes += sizes.get(ref, 0)
        if operand_bytes == 0:
            # fall back to the result type (exact for all-reduce)
            operand_bytes = _type_bytes(type_str)
        bytes_by[kind] += operand_bytes
    return CollectiveStats(bytes_by, count_by)


@dataclasses.dataclass
class Roofline:
    flops: float  # per-device HLO flops
    hbm_bytes: float  # per-device bytes accessed
    collective_bytes: float  # per-device collective operand bytes
    chips: int

    @property
    def t_compute(self) -> float:
        return self.flops / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.collective_bytes / ICI_BW

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def bound_time(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    def as_dict(self):
        return {
            "flops": self.flops,
            "hbm_bytes": self.hbm_bytes,
            "collective_bytes": self.collective_bytes,
            "chips": self.chips,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "dominant": self.dominant,
        }


def roofline_from_compiled(compiled, chips: int) -> tuple[Roofline, CollectiveStats]:
    cost = compiled.cost_analysis() or {}
    flops = float(cost.get("flops", 0.0))
    hbm = float(cost.get("bytes accessed", 0.0))
    colls = parse_collectives(compiled.as_text())
    return Roofline(flops, hbm, colls.total_bytes, chips), colls


def model_flops_train(cfg, tokens: int) -> float:
    """6·N_active·D analytic model FLOPs for one training step."""
    return 6.0 * cfg.active_param_count() * tokens


def model_flops_decode(cfg, tokens: int) -> float:
    """2·N_active per generated token (forward only)."""
    return 2.0 * cfg.active_param_count() * tokens
