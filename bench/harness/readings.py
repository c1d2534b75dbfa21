"""Quantities several metric readers share."""
from __future__ import annotations

from typing import List, Optional

import numpy as np


def pct(values: List[float], q: float) -> Optional[float]:
    """The q-th percentile (linear between order statistics), or None."""
    return float(np.percentile(values, q)) if values else None


def ttfts_ms(run) -> List[float]:
    """Time to first token of every request due in the window, timed from
    when it was due."""
    return [(r.req.t_first - r.due) * 1e3 for r in run.due_in_window()
            if r.req is not None and r.req.t_first is not None]


def tpots_ms(run) -> List[float]:
    """(t_finish - t_first) / (n_out - 1) of every request due in the
    window that emitted two tokens or more."""
    return [(r.req.t_finish - r.req.t_first) / (len(r.req.out) - 1) * 1e3
            for r in run.due_in_window()
            if r.req is not None and len(r.req.out) > 1]


def is_prefill_module(name: str) -> bool:
    return "_prefill_fn" in name


def is_decode_module(name: str) -> bool:
    return "_decode_fn" in name


def decode_roofline(run, is_kernel, per_row) -> Optional[float]:
    """A decode kernel's share of its roofline, in percent: the work the
    rows decoded in the traced window need (``per_row(cfg, ctx)`` gives
    one layer's (flops, bytes)), at the chip's peaks, over the time of
    the kernel's operations inside the decode program."""
    from bench.harness import reduce
    if run.trace is None or run.peaks is None or not run.decode_calls:
        return None
    secs, n = reduce.op_time(run.trace, is_kernel, is_decode_module)
    if not n:
        return None
    L = run.cfg["num_hidden_layers"]
    f = b = 0
    for rows in run.decode_calls:
        for p in rows:
            ff, bb = per_row(run.cfg, int(p) + 1)
            f, b = f + ff * L, b + bb * L
    bound = max(f / run.peaks["bf16_flops"], b / run.peaks["hbm_bytes_per_s"])
    return 100.0 * bound / secs
