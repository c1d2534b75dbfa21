"""mfu: model FLOPs of the tokens the program computed in the traced
window over (traced window x the chip's bf16 peak).  Each prefilled or
decoded token counts 2 x (non-embedding parameters + LM head), attention
over min(ctx, top_k) keys (all ctx without DSA) and the DSA indexer over
ctx (``bench.harness.work``); tokens served from the radix cache were
not computed and do not count."""
from bench.harness import reduce, work


def read(run):
    if run.trace is None or run.peaks is None \
            or not (run.prefill_calls or run.decode_calls):
        return None
    flops = sum(work.span_flops(run.cfg, start, n)
                for n, start in run.prefill_calls)
    flops += sum(work.token_flops(run.cfg, int(p))
                 for rows in run.decode_calls for p in rows)
    return flops / (reduce.window_s(run.trace)
                    * run.peaks["bf16_flops"]) * 100.0
