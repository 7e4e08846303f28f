"""Share of the device's busy time spent in the prefill-chunk program
(``Scheduler._chunk_fn``), from the device trace."""


def read(ctx):
    if ctx.trace is None or ctx.trace.busy_s <= 0:
        return None
    sec = ctx.trace.module_seconds("_chunk_fn")
    return 100.0 * sec / ctx.trace.busy_s if sec > 0 else None
