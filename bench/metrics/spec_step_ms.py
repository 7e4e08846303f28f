"""Device time of the decode-loop program (``engine._make_gen_loop``'s
``loop``) per spec step it ran in the window (the loop's own ``n_steps``
counter, differenced)."""


def read(ctx):
    if ctx.trace is None or ctx.n_steps <= 0:
        return None
    sec = ctx.trace.module_seconds("jit_loop")
    return 1e3 * sec / ctx.n_steps if sec > 0 else None
