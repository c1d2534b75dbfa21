"""Reduction of a device trace to busy time, idle share, kernel time and
the idle gaps' attribution.

Works on the normalized trace that ``bench.harness.tracing`` writes (the
format of ``bench/tests/data/trace_small.json``)::

    {"window": [open_ns, close_ns],
     "devices": [{"ops": [[name, start_ns, dur_ns, detail], ...],
                  "modules": [[name, start_ns, dur_ns], ...]}, ...],
     "host": [[name, start_ns, dur_ns], ...]}

Times are nanoseconds on the profiler's clock.  ``ops`` are the device's
XLA operations (kernels included) with their string stats as ``detail``,
``modules`` the compiled programs they ran in, ``host`` the benchmark's
own host spans.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Callable, Dict, List, Sequence, Tuple

Interval = Tuple[float, float]


def clip(events: Sequence, lo: float, hi: float) -> List[Interval]:
    """[start, end) of each event, cut to the window."""
    out = []
    for ev in events:
        s, e = ev[1], ev[1] + ev[2]
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e))
    return out


def union(iv: List[Interval]) -> List[Interval]:
    """Merge overlapping intervals."""
    out: List[list] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(trace: dict, device: int = 0) -> float:
    lo, hi = trace["window"]
    return sum(e - s for s, e in union(clip(trace["devices"][device]["ops"],
                                             lo, hi)))


def busy_s(trace: dict) -> float:
    """Seconds in which some operation ran, averaged over the devices."""
    n = len(trace["devices"])
    return sum(busy_ns(trace, d) for d in range(n)) / n / 1e9


def window_s(trace: dict) -> float:
    lo, hi = trace["window"]
    return (hi - lo) / 1e9


def idle_share(trace: dict) -> float:
    return 1.0 - busy_s(trace) / window_s(trace)


def in_modules(trace: dict, module: Callable[[str], bool],
               device: int = 0) -> List[Interval]:
    lo, hi = trace["window"]
    mods = [m for m in trace["devices"][device]["modules"] if module(m[0])]
    return union(clip(mods, lo, hi))


def module_calls(trace: dict, module: Callable[[str], bool],
                 device: int = 0) -> Tuple[float, int]:
    """(seconds, count) of the program runs that ``module`` selects and
    that lie wholly inside the window."""
    lo, hi = trace["window"]
    mods = [m for m in trace["devices"][device]["modules"]
            if module(m[0]) and m[1] >= lo and m[1] + m[2] <= hi]
    return sum(m[2] for m in mods) / 1e9, len(mods)


def op_time(trace: dict, op: Callable[[str], bool],
            module: Callable[[str], bool] = lambda n: True,
            device: int = 0) -> Tuple[float, int]:
    """(seconds, count) of the operations ``op`` selects (by name and
    detail) that ran inside a program ``module`` selects, within the
    window."""
    lo, hi = trace["window"]
    mods = in_modules(trace, module, device)
    starts = [m[0] for m in mods]
    total, n = 0.0, 0
    for ev in trace["devices"][device]["ops"]:
        if not op(" ".join(map(str, ev[:1] + ev[3:]))):
            continue
        s, e = ev[1], ev[1] + ev[2]
        if s < lo or e > hi:
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and e <= mods[i][1]:
            total += ev[2]
            n += 1
    return total / 1e9, n


_NUM = re.compile(r"[.\-_]?\d+$")
# operations whose time is that of the operations they contain
CONTAINERS = {"while", "conditional", "call"}


def op_name(text: str) -> str:
    """The HLO instruction's name: a TPU trace names an operation by its
    whole instruction (``%fusion.12 = bf16[...] fusion(...)``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def op_family(name: str) -> str:
    """An operation's name without its instance number (fusion.12 ->
    fusion), so that the breakdown groups like with like."""
    return _NUM.sub("", op_name(name))


def top_ops(trace: dict, k: int = 10, device: int = 0) -> List[list]:
    """The operation families that took most time in the window; a loop
    or call that holds other operations is left out, as its time is
    theirs."""
    lo, hi = trace["window"]
    tot: Dict[str, float] = defaultdict(float)
    for ev in trace["devices"][device]["ops"]:
        fam = op_family(ev[0])
        if fam in CONTAINERS:
            continue
        for s, e in clip([ev], lo, hi):
            tot[fam] += (e - s) / 1e9
    return [[n, v] for n, v in sorted(tot.items(), key=lambda x: -x[1])[:k]]


def gaps(trace: dict, device: int = 0) -> List[Interval]:
    """The window's intervals in which no operation ran."""
    lo, hi = trace["window"]
    out, t = [], lo
    for s, e in union(clip(trace["devices"][device]["ops"], lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def idle_by_host(trace: dict, k: int = 10, device: int = 0) -> List[list]:
    """Idle device time, summed by what the host was doing: each gap
    goes to the innermost host span covering its midpoint ("none" when
    no span does)."""
    host = sorted(trace["host"], key=lambda h: h[1])
    starts = [h[1] for h in host]
    tot: Dict[str, float] = defaultdict(float)
    for s, e in gaps(trace, device):
        mid = (s + e) / 2
        name = "none"
        # host spans of one thread nest: walking back from the last span
        # that started before ``mid``, the first that covers it is the
        # innermost
        first = bisect.bisect_right(starts, mid) - 1
        for i in range(first, max(first - 64, -1), -1):
            h = host[i]
            if mid < h[1] + h[2]:
                name = h[0]
                break
        tot[name] += (e - s) / 1e9
    return [[n, v] for n, v in sorted(tot.items(), key=lambda x: -x[1])[:k]]
