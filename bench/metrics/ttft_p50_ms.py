"""ttft_p50_ms: median time to first token over the requests due in the
window, timed from when each was due."""
from bench.harness.readings import pct, ttfts_ms


def read(run):
    return pct(ttfts_ms(run), 50)
