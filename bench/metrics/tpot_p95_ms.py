"""tpot_p95_ms: 95th percentile over the requests due in the window of
(t_finish - t_first) / (n_out - 1)."""
from bench.harness.readings import pct, tpots_ms


def read(run):
    return pct(tpots_ms(run), 95)
