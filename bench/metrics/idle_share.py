"""idle_share: 1 - (union of the device's busy intervals / traced
window), from the profiler's device trace."""
from bench.harness import reduce


def read(run):
    return None if run.trace is None else 100.0 * reduce.idle_share(run.trace)
