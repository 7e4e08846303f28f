"""Benchmark harness — one entry per paper table/figure + the roofline.

    PYTHONPATH=src python -m benchmarks.run [--only fig1,tab1,...] [--fast]

Prints ``name,key=value,...`` CSV lines; JSON artifacts land in
``artifacts/``."""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma list: fig1,tab1,fig2,kernels,spec_step,"
                         "spec_step_keyed,paged_decode,prefix_cache,"
                         "streaming,recurrent_draft,roofline")
    ap.add_argument("--fast", action="store_true",
                    help="reduced sample counts (CI mode)")
    ap.add_argument("--quick", action="store_true",
                    help="perf smoke: only the kernel + spec_step benches "
                         "at reduced sizes (produces kernels_bench.json "
                         "and spec_step_bench.json)")
    args = ap.parse_args()
    from repro.launch import compile_cache
    compile_cache.enable()
    only = set(args.only.split(",")) if args.only else None
    if args.quick:
        only = {"kernels", "spec_step", "spec_step_keyed", "paged_decode",
                "prefix_cache", "streaming", "recurrent_draft"}

    def want(name):
        return only is None or name in only

    failures = []

    def section(name, fn):
        print(f"### {name}", flush=True)
        t0 = time.time()
        try:
            fn()
            print(f"### {name} done in {time.time()-t0:.1f}s", flush=True)
        except Exception:  # noqa: BLE001
            traceback.print_exc()
            failures.append(name)

    if want("fig1"):
        from benchmarks import fig1_tradeoff
        section("fig1", lambda: fig1_tradeoff.run(
            n_seeds=8_000 if args.fast else 60_000,
            n_gamma=9 if args.fast else 17))
    if want("tab1"):
        from benchmarks import tab1_efficiency
        section("tab1", lambda: tab1_efficiency.run(
            n_tokens=24 if args.fast else 48,
            batch=4 if args.fast else 8))
    if want("fig2"):
        from benchmarks import fig2_detect
        section("fig2", fig2_detect.run)
    if want("kernels"):
        from benchmarks import kernels_bench
        section("kernels", kernels_bench.run)
    if want("spec_step"):
        from benchmarks import spec_step_bench
        section("spec_step", lambda: spec_step_bench.run(quick=args.quick))
    if want("spec_step_keyed"):
        from benchmarks import spec_step_bench
        section("spec_step_keyed",
                lambda: spec_step_bench.run_keyed(quick=args.quick))
    if want("paged_decode"):
        from benchmarks import spec_step_bench
        section("paged_decode",
                lambda: spec_step_bench.run_paged(quick=args.quick))
    if want("prefix_cache"):
        from benchmarks import spec_step_bench
        section("prefix_cache",
                lambda: spec_step_bench.run_prefix_cache(quick=args.quick))
    if want("streaming"):
        from benchmarks import spec_step_bench
        section("streaming",
                lambda: spec_step_bench.run_streaming(quick=args.quick))
    if want("recurrent_draft"):
        from benchmarks import recurrent_draft_bench
        section("recurrent_draft",
                lambda: recurrent_draft_bench.run(quick=args.quick
                                                  or args.fast))
    if want("roofline"):
        from benchmarks import roofline

        def _roofline():
            roofline.run(mesh_filter="")
            roofline.paged_decode_projection()
        section("roofline", _roofline)

    if failures:
        print(f"FAILED sections: {failures}")
        sys.exit(1)


if __name__ == "__main__":
    main()
