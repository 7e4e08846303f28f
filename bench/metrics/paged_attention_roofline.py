"""Roofline share of the ``paged_attention`` kernel: the time its calls
in the window need at the chip's peaks (live contexts only, bytes bound
for decode) over its device time in the trace."""
from harness import work


def read(ctx):
    if ctx.trace is None:
        return None
    sec = ctx.trace.op_seconds("paged_attention")
    if sec <= 0:
        return None
    t, d, K, nb = ctx.target, ctx.draft, ctx.K, ctx.dtype_bytes
    calls = [work.spec_step_attention(t, d, K, pos, nb) for pos in ctx.steps]
    calls += [work.prefill_attention(t, d, s, n, nb) for s, n in ctx.chunks]
    share, bound = work.roofline_share(calls, sec, ctx.peaks)
    ctx.note("paged_attention_roofline", f"{bound} bound")
    return share
