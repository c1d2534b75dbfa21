"""queue_wait_p95_ms: 95th percentile, over the requests due in the
window, of the time from when a request was due to the engine's
``req.admitted`` event (the program's tracer, on in traced runs)."""
from bench.harness.readings import pct


def read(run):
    waits = [(run.admitted[r.req.rid] - r.due) * 1e3
             for r in run.due_in_window()
             if r.req is not None and r.req.rid in run.admitted]
    return pct(waits, 95)
