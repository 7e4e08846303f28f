"""Accepted draft tokens per alive slot-step, from the scheduler's own
counts (``Scheduler.stats()``: aatps x alive_slot_steps and
alive_slot_steps, cumulative over flushed requests), differenced across
the window."""


def read(ctx):
    alive = ctx.counters1["alive"] - ctx.counters0["alive"]
    if alive <= 0:
        return None
    return (ctx.counters1["accepted"] - ctx.counters0["accepted"]) / alive
