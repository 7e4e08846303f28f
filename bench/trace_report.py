#!/usr/bin/env python3
"""Read the serving program's own spans, spec-step scopes and live
counters from one window of a cell, on the chip.

    python3 bench/trace_report.py --workload <cell> --seed <n> --seconds <s> \\
        [--trace 0|1] [--save <file.xplane.pb.gz>]

The set-up, traffic and window are ``bench/run.py``'s (a traced window is
at most its ``TRACE_SECONDS``); there is no check.  Prints one JSON line:
tokens/s visible in the window (a traced and an untraced run of one seed
give the cost of tracing), the sync rounds and ``slot_occupancy`` from
``Scheduler.stats()``, and, traced: ``round_idle_ms``, ``host_round_ms``,
``draft_share``, the loop's device seconds by spec-step phase, program
spans per round with each span's time in a round (median, largest, rounds
that ran it), and the longest device idle gaps named by the innermost
program span (``harness/spans.py``).  ``--save`` keeps the trace, recorded
without HLO protos, gzipped.
"""
from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run as bench_run  # noqa: E402  (bench/run.py: set-up and logging)


def serve_window(cell, seed, seconds, trace, compiles):
    """``bench/run.py``'s set-up, warm-up and window, with
    ``Scheduler.stats()`` read as the window opens and closes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from harness import serve as S
    from harness import trace as T
    from harness.traffic import Traffic
    from harness.weights import make_params
    c = cell.config
    dtype = jnp.dtype(c["dtype"])
    t_params = make_params(c, seed, 0, dtype)
    d_params = make_params(c["draft"], seed, 1, dtype)
    rng = np.random.default_rng(seed)
    sched = S.build_scheduler(cell, t_params, d_params,
                              int(rng.integers(1, 2**32 - 1)))
    S.warm_up(sched, cell, c["vocab_size"], rng, compiles, bench_run.log)
    cap = T.Capture() if trace else None
    stats = {}

    def on_open():
        if cap is not None:
            opts = jax.profiler.ProfileOptions()
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(cap.dir, profiler_options=opts)
            cap.on = True
        stats["open"] = sched.stats()

    def on_close():
        stats["close"] = sched.stats()
        if cap is not None:
            cap.stop()

    if trace:
        seconds = min(seconds, bench_run.TRACE_SECONDS)
    win = S.drive(sched, cell, Traffic(cell.traffic, seed, c["vocab_size"]),
                  seconds, compiles, annotate=jax.profiler.TraceAnnotation
                  if trace else None, on_open=on_open, on_close=on_close)
    return win, stats, cap, sched.B


def read_trace(raw, device_id):
    """The span readings of one trace (``raw``: its XSpace bytes)."""
    from jax.profiler import ProfileData

    from harness import spans as SP
    from harness import trace as T
    pd = ProfileData.from_serialized_xspace(raw)
    red = T.reduce(pd, device_ids=[device_id])
    plane = next(iter(red.devices))
    spans = SP.host_spans(pd)
    t0, t1 = SP.window(red, spans)
    busy = red.busy_intervals(plane)
    ops = SP.scoped_ops(pd, SP.op_scopes(raw, plane), plane, t0, t1)
    rounds = SP.rounds(spans, t0, t1)
    inner = [[s for s in SP.inside(spans, r)
              if s.name.startswith("serve.") and s is not r] for r in rounds]
    per_round = {}
    for kids in inner:
        for name in {s.name for s in kids}:
            per_round.setdefault(name, []).append(sum(
                s.end - s.start for s in kids if s.name == name) * 1e-6)
    return {
        "round_idle_ms": SP.round_idle_ms(spans, busy, t0, t1),
        "host_round_ms": SP.host_round_ms(spans, t0, t1),
        "draft_share": SP.draft_share(ops),
        "loop_phase_s": SP.phase_seconds(ops),
        "whole_rounds": len(rounds),
        "spans_per_round": (sum(map(len, inner)) / len(rounds)
                            if rounds else None),
        "span_ms": {n: [statistics.median(v), max(v), len(v)]
                    for n, v in sorted(per_round.items())},
        "busy_s": red.busy_s, "window_s": red.window_s,
        "idle_gaps": [[SP.gap_name(g, spans), (g[1] - g[0]) * 1e-9]
                      for g in SP.idle_gaps(busy, t0, t1)[:10]],
        "idle_gaps_bench": red.idle_gaps(10),
    }


def report(cell, seed, seconds, trace, save=None):
    bench_run.setup_jax()
    import jax

    from harness import spans as SP
    from harness import window as W
    from harness.serve import Compiles
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        bench_run.log(f"no TPU (platform={dev.platform})")
        return 1
    compiles = Compiles()
    try:
        win, stats, cap, batch = serve_window(cell, seed, seconds, trace,
                                              compiles)
    finally:
        compiles.close()
    s0, s1 = stats["open"], stats["close"]
    res = {"workload": cell.name, "seed": seed, "traced": bool(trace),
           "tokens_per_s": W.end_to_end(win)["tokens_per_s"],
           "compiles": win.compiles, "error": win.error,
           "rounds": s1["rounds"] - s0["rounds"],
           "slot_occupancy": SP.slot_occupancy(s0, s1, batch)}
    if cap is not None:
        try:
            path = sorted(glob.glob(os.path.join(
                cap.dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
            with open(path, "rb") as f:
                raw = f.read()
            if save:
                with gzip.open(save, "wb") as f:
                    f.write(raw)
                res["saved_bytes"] = os.path.getsize(save)
            t = time.perf_counter()
            res.update(read_trace(raw, dev.id))
            res["read_s"] = time.perf_counter() - t
        finally:
            cap.close()
    print(json.dumps(res))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10)
    ap.add_argument("--trace", type=int, default=1, choices=(0, 1))
    ap.add_argument("--save", default="")
    args = ap.parse_args(argv)
    from harness.cell import load_cell
    return report(load_cell(args.workload), args.seed, args.seconds,
                  bool(args.trace), args.save or None)


if __name__ == "__main__":
    sys.exit(main())
