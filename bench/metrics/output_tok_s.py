"""output_tok_s: every output token sampled inside the window, over the
window's length.  Tokens from decode steps are the engine's
``decode_tokens`` counter between the window's two snapshots; each
request's first token (sampled at the end of its prefill) counts when
its t_first falls inside the window."""


def read(run):
    w = run.window
    if "t0" not in w.marks or "t1" not in w.marks:
        return None
    firsts = sum(1 for r in w.recs if r.req is not None
                 and r.req.t_first is not None
                 and w.marks["t0"] <= r.req.t_first < w.marks["t1"])
    return (run.delta("decode_tokens") + firsts) / (w.marks["t1"]
                                                    - w.marks["t0"])
