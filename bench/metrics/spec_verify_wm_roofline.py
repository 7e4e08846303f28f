"""Roofline share of the fused ``spec_verify_wm`` verify tail: the time
its live rows in the window need at the chip's peaks (reading the float32
probability rows, bytes bound) over its device time in the trace."""
from harness import work


def read(ctx):
    if ctx.trace is None:
        return None
    sec = ctx.trace.op_seconds("spec_verify_wm")
    if sec <= 0 or not ctx.steps:
        return None
    rounds = int(ctx.spec.get("m", 30)) \
        if ctx.spec["watermark"] == "synthid" else 0
    row = work.verify_tail(ctx.target["vocab_size"], ctx.K, rounds)
    share, bound = work.roofline_share([row] * len(ctx.steps), sec,
                                       ctx.peaks)
    ctx.note("spec_verify_wm_roofline", f"{bound} bound")
    return share
