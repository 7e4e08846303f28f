"""FLOPs the window executed over the window's length times the chip's
bf16 peak: target and draft over every live slot-step's K+1 positions,
and every valid prefill token, counted from shapes (``work.py``)."""
from harness import work


def read(ctx):
    t, d, K = ctx.target, ctx.draft, ctx.K
    flops = sum(work.spec_step_flops(t, d, K, pos) for pos in ctx.steps)
    flops += sum(work.prefill_flops(t, d, s, n) for s, n in ctx.chunks)
    if flops <= 0 or ctx.window_s <= 0:
        return None
    return 100.0 * flops / (ctx.window_s * ctx.peaks["flops"] * ctx.chips)
