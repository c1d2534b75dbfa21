"""prefill_chunk_ms: device time per call of the jitted prefill program
(the engine's ``_prefill_fn``), from the ``XLA Modules`` line of the
device trace."""
from bench.harness import reduce
from bench.harness.readings import is_prefill_module


def read(run):
    if run.trace is None:
        return None
    secs, n = reduce.module_calls(run.trace, is_prefill_module)
    return secs / n * 1e3 if n else None
