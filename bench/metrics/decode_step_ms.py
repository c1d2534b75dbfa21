"""decode_step_ms: device time per call of the jitted decode program
(the engine's ``_decode_fn``), from the ``XLA Modules`` line of the
device trace."""
from bench.harness import reduce
from bench.harness.readings import is_decode_module


def read(run):
    if run.trace is None:
        return None
    secs, n = reduce.module_calls(run.trace, is_decode_module)
    return secs / n * 1e3 if n else None
