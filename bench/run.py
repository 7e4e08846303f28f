#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout; one process holds the chip.  Phases:

1. set-up: device check (platform, device kind, count; anything but a
   TPU, or fewer chips than the cell asks for, exits 1 with no result),
   the persistent compile cache, bf16 weights made on the device from the
   seed, the program's ``Scheduler`` over its paged pool, warm-up passes
   until nothing new compiles, and the traffic's ramp;
2. the measured window: the cell's traffic for ``--seconds`` (traced
   with ``--trace 1``), then what is in flight drains;
3. the check: sampled served requests against the plain float32
   reference (``harness/check.py``), after the program's state is freed;
4. the result: the numbers compared, each beside its limit, on standard
   error, then one JSON line on standard output with ``correct``,
   ``attempted``, ``failed``, ``metrics``, ``device`` (and, traced,
   ``breakdown``) and last ``checked``.

``--readings 1`` also reads the control (the reference in float8 weights
in the program's place) on the same requests and judges it by the same
limits, and ``--seeds a,b,..`` runs one window per seed in this one
process; both are for setting the limits and print readings instead of
a result.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Ctx:
    """What a per-layer reader (``metrics/<name>.py``) reads."""

    def __init__(self, **kw):
        self.__dict__.update(kw)
        self.notes = {}

    def note(self, name, text):
        self.notes[name] = text


def load_reader(name: str):
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("bench_metric_" + name,
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def setup_jax(cache=True):
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    if not cache:
        return None
    from repro.launch import compile_cache
    cache = compile_cache.enable()
    # every program, however quick to compile, comes from the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache


# a traced run traces at most this long a window: the trace of a longer
# one takes minutes to read back
TRACE_SECONDS = 20.0


def serve_once(cell, seed, seconds, trace, compiles, device, drain_s=60.0):
    """Set-up (from weights on), window and drain for one seed; returns
    everything the check and the result need.  A traced run's window is
    at most ``TRACE_SECONDS``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from harness import serve as S
    from harness import trace as T
    from harness.traffic import Traffic
    from harness.weights import make_params
    c = cell.config
    dtype = jnp.dtype(c["dtype"])
    t0 = time.perf_counter()
    t_params = make_params(c, seed, 0, dtype)
    d_params = make_params(c["draft"], seed, 1, dtype)
    log(f"  weights: {time.perf_counter() - t0:.2f} s")
    rng = np.random.default_rng(seed)
    key_word = int(rng.integers(1, 2**32 - 1))
    sched = S.build_scheduler(cell, t_params, d_params, key_word)
    try:
        passes = S.warm_up(sched, cell, c["vocab_size"], rng, compiles, log)
    except Exception as e:          # the served path failed: not correct
        passes, now = 0, time.perf_counter()
        win = S.Window(start=now, end=now, reqs={}, compiles=0,
                       max_late_s=0.0, drained=False,
                       error=f"warm-up: {type(e).__name__}: {e}")
        return dict(window=win, served={}, marks={}, passes=0,
                    key_word=key_word, t_params=t_params, d_params=d_params,
                    memory_peak=0, capture=None)
    traffic = Traffic(cell.traffic, seed, c["vocab_size"])
    cap = T.Capture() if trace else None
    marks = {}

    def counters_at(tag):
        marks[tag] = {"counters": S.counters(sched),
                      "n_steps": int(jax.device_get(sched.carry["n_steps"])),
                      "t": time.perf_counter()}

    def on_open():
        if cap is not None:
            cap.start()
        counters_at("open")

    def on_close():
        counters_at("close")
        if cap is not None:
            cap.stop()

    annotate = (lambda name: jax.profiler.TraceAnnotation(name)) \
        if trace else None
    if trace:
        seconds = min(seconds, TRACE_SECONDS)
    win = S.drive(sched, cell, traffic, seconds, compiles,
                  annotate=annotate, on_open=on_open, on_close=on_close,
                  drain=trace, drain_s=drain_s)
    stats = device.memory_stats() or {}
    served = {uid: S.served(sched, uid) for uid in win.reqs}
    served = {u: s for u, s in served.items() if s is not None}
    out = dict(window=win, served=served, marks=marks, passes=passes,
               key_word=key_word, t_params=t_params, d_params=d_params,
               memory_peak=int(stats.get("peak_bytes_in_use", 0)),
               capture=cap)
    del sched
    gc.collect()
    return out


def check(cell, run, seed, control=False, sizes=None):
    """The sampled requests' gaps against the reference.  ``sizes`` (for
    the readings) reads a sample of each of these numbers of served tokens
    instead of the configuration's one; the samples share their order, so
    the smaller ones are the start of the larger."""
    from harness import check as C
    w = run["window"]
    done = [u for u, r in w.reqs.items() if r.done and u in run["served"]]
    lengths = {u: len(run["served"][u]["tokens"]) for u in done}
    c = cell.config["check"]
    want = sizes or [int(c["tokens"])]
    max_requests = int(c["max_requests"]) * (4 if sizes else 1)
    picks = C.sample(done, lengths, seed, max(want), max_requests)
    per = []
    for u in picks:
        s = run["served"][u]
        per.append(C.request_gaps(
            run["t_params"], run["d_params"], cell.config,
            cell.config["draft"], w.reqs[u].prompt, s["tokens"], s["src"],
            run["key_word"], cell.spec, control=control))
    out = {}
    for size in want:
        n = len(C.sample(done, lengths, seed, size, max_requests))
        rd = C.readings(per[:n], control=control)
        rd["requests"] = n
        rd["tokens"] = int(sum(lengths[u] for u in picks[:n]))
        out[size] = rd
    return out if sizes else out[want[0]]


def judge(rd, limits, prefix=""):
    """Whether the readings ``rd`` (the program's, or with ``prefix=
    "control_"`` the control's) are within ``limits``, and each number
    beside its limit."""
    ok = rd["requests"] > 0
    checked = {}
    for k, lim in limits.items():
        v = rd.get(prefix + k)
        ok = ok and v is not None and v <= lim
        # JSON has no infinity: a gap that is not finite is written as text
        checked[k] = {"value": v if v is None or math.isfinite(v)
                      else str(v), "limit": lim}
    return ok, checked


def per_layer(cell, run, device):
    from harness import trace as T
    from harness import window as W
    from harness import work
    from harness.cell import dtype_bytes
    marks = run["marks"]
    steps, chunks = W.steps_and_chunks(cell, run["window"], run["served"],
                                       marks["open"]["t"],
                                       marks["close"]["t"])
    red = None
    if run["capture"] is not None:
        red = T.reduce(run["capture"].load(), device_ids=[device.id])
        run["capture"].close()
    ctx = Ctx(cell=cell, trace=red, steps=steps, chunks=chunks,
              counters0=marks["open"]["counters"],
              counters1=marks["close"]["counters"],
              n_steps=marks["close"]["n_steps"] - marks["open"]["n_steps"],
              window_s=marks["close"]["t"] - marks["open"]["t"],
              target=cell.config, draft=cell.config["draft"],
              K=int(cell.spec["K"]), spec=cell.spec,
              dtype_bytes=dtype_bytes(cell.config),
              peaks=work.peaks(device.device_kind), chips=cell.chips)
    metrics = {}
    for m in cell.reported("per_layer"):
        v = load_reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return metrics, red, ctx.notes


def run(cell, seed, seconds, trace, require_tpu=True, out=print,
        cache=True, drain_s=60.0):
    """One run of ``cell``; prints the result line through ``out``.
    ``require_tpu=False`` and ``cache=False`` let the tests drive a run
    on the CPU."""
    t_setup = time.perf_counter()
    cache = setup_jax(cache)
    import jax

    from harness.serve import Compiles
    from harness import window as W
    devices = jax.devices()
    dev = devices[0]
    log(f"device: platform={dev.platform} device_kind={dev.device_kind} "
        f"device_count={len(devices)} compile_cache={cache}")
    if require_tpu and dev.platform != "tpu":
        log(f"no TPU (platform={dev.platform}): this benchmark runs on the "
            "chip only")
        return 1
    if len(devices) < cell.chips:
        log(f"cell {cell.name} needs {cell.chips} chips, found "
            f"{len(devices)}")
        return 1
    log(f"cell {cell.name}: batch={cell.batch} pages={cell.num_pages} "
        f"({cell.pages_per_slot}/slot)")
    compiles = Compiles()
    try:
        r = serve_once(cell, seed, seconds, trace, compiles, dev, drain_s)
    finally:
        compiles.close()
    w = r["window"]
    setup_s = w.start - t_setup
    log(f"window: {seconds} s; programs lowered in the window: "
        f"{w.compiles}; warm-up passes {r['passes']}; open-loop "
        f"lateness max {w.max_late_s:.4f} s")
    if w.error:
        log(f"served path failed: {w.error}")
    e2e = W.end_to_end(w)
    log(f"requests with a first token in the window: {e2e['n_ttft']}, "
        f"completed in it: {e2e['n_tpot']}")
    metrics, breakdown = {}, None
    if trace:
        metrics, red, notes = per_layer(cell, r, dev)
        for k, v in notes.items():
            log(f"{k}: {v}")
        if red is not None:
            breakdown = {"device_ops": red.top_ops(10),
                         "idle_gaps": red.idle_gaps(10)}
    else:
        values = dict(e2e, setup_s=setup_s)
        for m in cell.reported("end_to_end"):
            v = values.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    # a request is due by the window's close; it fails where the served
    # path fails, or where a drained run never finishes it
    attempted = [u for u, q in w.reqs.items() if q.t0 <= w.end]
    failed = [u for u in attempted if not w.reqs[u].done] \
        if (w.error or w.drained) else []
    limits = cell.config["check"]["limits"]
    rd = check(cell, r, seed) if not w.error else \
        dict({k: None for k in limits}, requests=0, tokens=0)
    within, checked = judge(rd, limits)
    ok = w.error is None and not failed and within
    checked["failed_requests"] = {"value": len(failed), "limit": 0}
    log(f"checked {rd['requests']} requests, {rd['tokens']} served tokens; "
        f"widest token gap {rd.get('token_gap_widest')}, widest accept gap "
        f"{rd.get('accept_gap_widest')} (not compared); "
        f"{rd.get('decisions')} decisions read, "
        f"{rd.get('decisions_wrong')} on the wrong side, widest "
        f"{rd.get('accept_gap_top')}")
    for k, v in checked.items():
        log(f"{k}: {v['value']} (limit {v['limit']})")
    res = {"correct": bool(ok), "attempted": len(attempted),
           "failed": len(failed), "metrics": metrics,
           "device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(devices),
                      "memory_peak_bytes": r["memory_peak"]}}
    if trace and breakdown is not None:
        res["device"]["busy_s"] = red.busy_s
        res["device"]["window_s"] = red.window_s
        res["breakdown"] = breakdown
    res["checked"] = checked
    out(json.dumps(res))
    return 0


def readings(cell, seeds, seconds, sizes=None, control_seeds=3):
    """One window per seed in this process; prints the program's readings
    per seed, and the control's on the first ``control_seeds``; with
    ``sizes``, one line per sample size."""
    setup_jax()
    import jax

    from harness.serve import Compiles
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        log(f"no TPU (platform={dev.platform})")
        return 1
    compiles = Compiles()
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        r = serve_once(cell, seed, seconds, False, compiles, dev)
        w = r["window"]
        t1 = time.perf_counter()
        control = i < control_seeds
        by_size = check(cell, r, seed, control=control,
                        sizes=sizes or [int(cell.config["check"]["tokens"])])
        check_s = time.perf_counter() - t1
        limits = cell.config["check"]["limits"]
        for size, rd in by_size.items():
            rd["correct"] = judge(rd, limits)[0] and w.error is None
            if control:
                ok, checked = judge(rd, limits, "control_")
                rd["control_correct"] = ok
                log(f"seed {seed}, {size} tokens: the control in the "
                    f"program's place is "
                    f"{'correct' if ok else 'not correct'}: {checked}")
            rd.update(seed=seed, size=size, error=w.error, serve_s=t1 - t0,
                      check_s=check_s,
                      done=sum(q.done for q in w.reqs.values()),
                      memory_peak=r["memory_peak"])
            print("READING " + json.dumps(rd), flush=True)
        del r
        gc.collect()
    compiles.close()
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--readings", type=int, default=0, choices=(0, 1))
    ap.add_argument("--seeds", default="")
    ap.add_argument("--sizes", default="",
                    help="readings: sample sizes in served tokens, a,b,..")
    args = ap.parse_args(argv)
    from harness.cell import load_cell
    cell = load_cell(args.workload)
    if args.readings:
        seeds = [int(s) for s in args.seeds.split(",") if s] or [args.seed]
        sizes = [int(s) for s in args.sizes.split(",") if s] or None
        return readings(cell, seeds, args.seconds, sizes)
    return run(cell, args.seed, args.seconds, bool(args.trace))


if __name__ == "__main__":
    sys.exit(main())
