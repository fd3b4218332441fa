"""Model FLOPs of every prompt and output token the window processed over
the window's chip-time at the chip's bf16 peak (``counts.token_flops``:
two per multiplied parameter, and attention at each token's context)."""
import counts
from _common import decode_tokens, prefilled

NAME = "mfu"
UNIT = "%"
LAYER = "whole step"


def compute(ctx):
    rec, c = ctx["rec"], ctx["config"]
    flops = sum(counts.prefill_flops(c, f.req.prompt_len)
                for f in prefilled(rec))
    flops += sum(counts.token_flops(c, keys) for _, keys in decode_tokens(rec))
    if not flops:
        return None
    peak = ctx["peaks"]["bf16_flops"] * ctx["chips"]
    return 100.0 * flops / ((rec.t1 - rec.t0) * peak)
