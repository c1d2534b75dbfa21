"""prefix_hit_share: share of the prompt tokens admitted in the window
that the radix cache served, cached / (cached + prefilled), from the
engine's ``cached_tokens`` and ``prefill_tokens`` counters."""


def read(run):
    c, p = run.delta("cached_tokens"), run.delta("prefill_tokens")
    return 100.0 * c / (c + p) if c + p else None
