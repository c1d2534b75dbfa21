"""itl_p99_ms: 99th percentile of the gap between consecutive tokens,
pooled over the requests due in the window, from the per-token stamps the
engine keeps on each request (``t_tokens``).  None where requests carry
no stamps."""
import numpy as np

from bench.harness.readings import pct


def read(run):
    gaps = []
    for r in run.due_in_window():
        t = getattr(r.req, "t_tokens", None)
        if t is not None and len(t) > 1:
            gaps.extend(np.diff(t) * 1e3)
    return pct(gaps, 99)
