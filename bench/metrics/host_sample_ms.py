"""host_sample_ms: host milliseconds the engine spends sampling per decode
step over the window, its ``sample_ns`` counter over its ``decode_steps``.
None where the engine keeps no ``sample_ns`` counter."""


def read(run):
    w = run.window
    if "sample_ns" not in w.stats0 or "sample_ns" not in w.stats1:
        return None
    steps = run.delta("decode_steps")
    return run.delta("sample_ns") / steps / 1e6 if steps else None
