"""decode_batch_mean: rows per decode step in the window, the engine's
``decode_tokens`` over ``decode_steps``."""


def read(run):
    s = run.delta("decode_steps")
    return run.delta("decode_tokens") / s if s else None
