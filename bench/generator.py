"""The one traffic generator: reads a mix's parameters (``bench/traffic/
<mix>.json``) and lays out, from the seed, the calls a run makes.

Mix keys:

* ``loop``: ``closed`` (one client that waits for each answer) or
  ``open`` (arrivals on a schedule, whatever the answers do);
* ``calls``: ``passes`` (the configuration's statements in its order,
  whole passes) or ``mix`` (statements in rounds of ``shares`` calls
  each, ``"equal"`` for one each, as each query runs once in a TPC-H
  query stream; each statement draws its own bindings);
* ``rate_per_s`` (open): arrivals per second, Poisson;
* ``cycle`` (closed mix): calls laid out, repeated as needed;
* ``checked``: calls whose answers are kept and compared.

Every seed gets the same amount of work: an open mix has exactly
``round(rate_per_s * seconds)`` arrivals whose gaps are one fixed set of
exponential quantiles, and each statement exactly its share of the
calls; the seed only orders them and draws the bindings and the data.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Request:
    stmt: str
    params: dict | None
    due: float | None = None   # seconds after the window opens (open loop)


@dataclasses.dataclass
class Plan:
    requests: list
    checked: set | None        # calls whose answers are compared; None: all
    cyclic: bool = False       # a closed loop repeats the list as needed


def shares(mix: dict, names: list[str]) -> dict[str, int]:
    """Calls of each statement per round of the mix (``"equal"``: one)."""
    s = mix.get("shares", "equal")
    if s == "equal":
        return {n: 1 for n in names}
    unknown = set(s) - set(names)
    if unknown:
        raise ValueError(f"mix names statements the config lacks: {unknown}")
    return {n: int(k) for n, k in s.items()}


def poisson_gaps(n: int, rate: float) -> np.ndarray:
    """``n`` inter-arrival gaps: the midpoint quantiles of an exponential
    distribution of mean ``1 / rate`` (their order is the seed's)."""
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate


def plan(mix: dict, statements: dict, data, rng, seconds: float) -> Plan:
    """The calls of one run; ``statements`` maps names to statement
    modules in the configuration's order."""
    names = list(statements)
    if mix["calls"] == "passes":
        return Plan([Request(n, None) for n in names], None, cyclic=True)
    n = int(round(mix["rate_per_s"] * seconds)) if mix["loop"] == "open" \
        else int(mix["cycle"])
    # rounds that each hold every statement its share of times, in the
    # seed's order: any stretch of calls a window reaches has the shares
    per_round = [k for k, c in shares(mix, names).items() for _ in range(c)]
    order: list[str] = []
    while len(order) < n:
        order += [per_round[i] for i in rng.permutation(len(per_round))]
    order = order[:n]
    binds = {k: iter(statements[k].bindings(rng, order.count(k), data))
             for k in names if k in order}
    reqs = [Request(k, next(binds[k])) for k in order]
    if mix["loop"] == "open":
        due = np.cumsum(rng.permutation(poisson_gaps(n, mix["rate_per_s"])))
        for r, t in zip(reqs, due):
            r.due = float(t)
    checked = set(rng.choice(n, min(n, int(mix["checked"])),
                             replace=False).tolist())
    return Plan(reqs, checked, cyclic=mix["loop"] == "closed")
