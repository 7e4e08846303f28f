"""Serving launcher: watermarked speculative decoding for any assigned
architecture (reduced config on CPU; ``--dry-run`` lowers the full config's
serve step on the production mesh).

    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-3b \
        --watermark gumbel --k 3 --tokens 32

Mesh-aware serving: ``--mesh DATAxMODEL`` runs the engine sharded over a
host mesh (state/buffers batch-sharded, params by the production rules).
``--devices N`` forces N fake CPU devices (must be the first jax init), so
the sharded path validates on one machine:

    PYTHONPATH=src python -m repro.launch.serve --devices 8 --mesh 8x1

Continuous batching: ``--requests FILE.jsonl`` replays a request log
through the scheduler (``engine.serve_requests``) instead of one fixed
batch — each line is ``{"tokens": [...], "n_tokens": N}`` (or ``{"text":
"...", ...}``, byte-encoded with the synthetic vocab); prompts are
admitted FIFO into ``--batch`` live slots at sync points:

    PYTHONPATH=src python -m repro.launch.serve --requests reqs.jsonl \
        --batch 4 --sync-every 4 [--eos-id 10]

Multi-tenant keys: a request line may carry ``"key": <int>`` (serve that
request under an explicit watermark key word) and/or ``"tier":
"latency"|"balanced"|"assurance"`` (map the tier to a watermark strength
gamma on the trade-off curve).  ``--key-pool N`` serves keyless requests
from a rotating N-word ``serve.keys.KeyPool`` instead of the single
launch key.  The replay report prints each request's 8-hex key
fingerprint — the only key identifier that ever leaves the process.
Unknown request fields are a hard error (a typo must not silently serve
under default keying).

Streaming & overlap: ``--stream`` prints every token as it surfaces at a
sync point (the ``on_token`` consumer surface) and the replay report
always includes per-request TTFT / inter-token-gap aggregates plus the
prefix-cache hit/saved/eviction counters; ``--overlap`` double-buffers
the loop (dispatch chunk N+1 before flushing chunk N — same served
bits, see docs/serving.md).
"""
from __future__ import annotations

import argparse
import json
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--draft-arch", default="",
                    help="draft model architecture (e.g. rwkv6-3b for a "
                         "recurrent drafter under an attention target); "
                         "default: a 1-layer dense draft of --arch's family")
    ap.add_argument("--watermark", default="gumbel",
                    choices=["gumbel", "synthid", "synthid-inf", "none"])
    ap.add_argument("--accept", default="pseudorandom",
                    choices=["pseudorandom", "standard"])
    ap.add_argument("--k", type=int, default=3)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.7)
    ap.add_argument("--dry-run", action="store_true")
    ap.add_argument("--shape", default="decode_32k",
                    choices=["decode_32k", "long_500k"])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--mesh", default="",
                    help="run the engine sharded on a DATAxMODEL host mesh "
                         "(e.g. 8x1); batch must divide the data ways")
    ap.add_argument("--devices", type=int, default=0,
                    help="force N fake CPU devices before jax init "
                         "(single-machine validation of --mesh)")
    ap.add_argument("--requests", default="",
                    help="continuous-batching replay: JSONL file of "
                         '{"tokens": [...], "n_tokens": N} requests '
                         "served FIFO through --batch live slots (each "
                         "distinct prompt length compiles its own "
                         "prefill — bucket lengths in the file for "
                         "length-diverse traffic)")
    ap.add_argument("--sync-every", type=int, default=4,
                    help="scheduler sync-point interval (steps between "
                         "admission/flush opportunities)")
    ap.add_argument("--eos-id", type=int, default=-1,
                    help="token id that terminates a slot early "
                         "(-1 = disabled)")
    ap.add_argument("--page-size", type=int, default=0,
                    help="block-paged KV cache: tokens per page (0 = "
                         "dense caching); requires --num-pages")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="block-paged KV cache: physical pages in the "
                         "shared pool (page 0 is the reserved null page)")
    ap.add_argument("--key-pool", type=int, default=0,
                    help="serve keyless requests from a rotating pool of "
                         "N watermark key words derived from the launch "
                         "key (0 = single shared key)")
    ap.add_argument("--prefill-chunk", type=int, default=8,
                    help="paged admission prefills prompts in chunks of "
                         "this many tokens (one fixed compile, no decode "
                         "stall on long prompts)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share identical full-page prompt prefixes "
                         "(system prompts, few-shot headers) across "
                         "requests via refcounted KV pages; requires "
                         "--page-size/--num-pages; results stay "
                         "bit-identical to solo generation")
    ap.add_argument("--stream", action="store_true",
                    help="print each token as it surfaces at a sync "
                         "point (uid=.. i=.. tok=..) — the on_token "
                         "consumer surface; the replay report gains "
                         "TTFT / inter-token-gap aggregates either way")
    ap.add_argument("--overlap", action="store_true",
                    help="double-buffer the serving loop: dispatch the "
                         "next decode chunk before the host-side "
                         "flush/admission of the previous one (served "
                         "bits unchanged; paged pools need the doubled "
                         "page-growth horizon — see docs/serving.md)")
    args = ap.parse_args()

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}")

    if args.dry_run:
        import subprocess
        import sys
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               "--arch", args.arch, "--shape", args.shape]
        if args.multi_pod:
            cmd.append("--multi-pod")
        raise SystemExit(subprocess.call(cmd, env=dict(
            os.environ, PYTHONPATH=os.environ.get("PYTHONPATH", "src"))))

    from repro.launch import compile_cache
    compile_cache.enable()

    import jax
    import numpy as np

    from repro.configs import get_smoke_config
    from repro.data import synthetic
    from repro.models import model as M
    from repro.serve import engine as E

    tcfg = get_smoke_config(args.arch, vocab=synthetic.VOCAB)
    dcfg = get_smoke_config(args.arch, vocab=synthetic.VOCAB, n_layers=1,
                            d_model=64, d_ff=128, n_heads=2, n_kv_heads=2,
                            head_dim=32)
    if args.draft_arch:
        dcfg = get_smoke_config(args.draft_arch, vocab=synthetic.VOCAB,
                                n_layers=1, d_model=64)
    elif tcfg.arch_type in ("ssm", "hybrid"):
        # draft stays a small dense transformer, as in deployment
        dcfg = get_smoke_config("yi-6b", vocab=synthetic.VOCAB, n_layers=1,
                                d_model=64, d_ff=128, n_heads=2,
                                n_kv_heads=2, head_dim=32)
    key = jax.random.key(0)
    t_params = M.init_params(jax.random.key(1), tcfg)
    d_params = M.init_params(jax.random.key(2), dcfg)
    corpus = synthetic.SyntheticCorpus()
    rows = []
    for p in synthetic.prompts(corpus, args.batch, prompt_words=3):
        p = p[:12]
        p = np.concatenate([np.zeros(12 - len(p), np.int32), p])
        rows.append(p)
    prompts = jax.numpy.asarray(np.stack(rows))
    extras = None
    if tcfg.arch_type in ("audio", "vlm"):
        b = M.example_batch(tcfg, args.batch, 4)
        extras = {k: v for k, v in b.items() if k != "tokens"}
    scfg = E.SpecConfig(K=args.k, watermark=args.watermark,
                        accept=args.accept, temperature=args.temperature)
    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_host_mesh
        data, model = (int(x) for x in args.mesh.split("x"))
        mesh = make_host_mesh(data=data, model=model)
        print(f"serving sharded on {mesh}")

    if args.requests:
        allowed = {"tokens", "text", "n_tokens", "key", "tier", "uid"}
        reqs = []
        with open(args.requests) as fh:
            for ln, line in enumerate(fh, 1):
                line = line.strip()
                if not line:
                    continue
                obj = json.loads(line)
                unknown = sorted(set(obj) - allowed)
                if unknown:
                    ap.error(f"{args.requests}:{ln}: unknown request "
                             f"fields {unknown} — accepted: "
                             f"{sorted(allowed)}")
                toks = (obj["tokens"] if "tokens" in obj else
                        synthetic.encode(obj["text"].encode()).tolist())
                req = {"prompt": np.asarray(toks, np.int32),
                       "n_tokens": int(obj.get("n_tokens", args.tokens))}
                for fld in ("key", "tier", "uid"):
                    if fld in obj:
                        req[fld] = obj[fld]
                reqs.append(req)
        eos = None if args.eos_id < 0 else args.eos_id
        if args.page_size and not args.num_pages:
            ap.error("--page-size requires --num-pages")
        if args.prefix_cache and not args.page_size:
            ap.error("--prefix-cache requires --page-size/--num-pages "
                     "(prefix sharing lives on the paged KV pool)")
        from repro.serve import keys as KZ
        pool = (KZ.KeyPool(key, n_keys=args.key_pool)
                if args.key_pool else None)
        ctrl = None
        if any("tier" in r for r in reqs):
            # modest MC budget: the CLI picks gammas, it doesn't publish
            # the paper curve
            ctrl = KZ.StrengthController(decoder_name=args.watermark,
                                         n_seeds=4000, n_gamma=9)
        on_token = None
        if args.stream:
            def on_token(uid, tok, meta):
                fin = " final" if meta["final"] else ""
                print(f"  stream uid={uid} i={meta['index']} tok={tok} "
                      f"t={meta['t_rel_s']:.3f}s{fin}")
        stats: dict = {}
        from repro.serve.config import ServeConfig
        serve_cfg = ServeConfig(
            batch=args.batch, key=key, eos_id=eos,
            sync_every=args.sync_every, mesh=mesh,
            page_size=args.page_size or None,
            num_pages=args.num_pages or None,
            prefill_chunk=args.prefill_chunk if args.page_size else None,
            prefix_cache=args.prefix_cache,
            key_pool=pool, strength_controller=ctrl,
            overlap=args.overlap, on_token=on_token)
        results = E.serve_requests(t_params, d_params, tcfg, dcfg, scfg,
                                   reqs, config=serve_cfg, stats_out=stats)
        tot = sum(r.length for r in results)
        alive = sum(r.alive_steps for r in results)
        acc = sum(r.n_accepted for r in results)
        paged = (f" paged(page_size={args.page_size}, "
                 f"num_pages={args.num_pages}"
                 + (", prefix-cache" if args.prefix_cache else "") + ")"
                 if args.page_size else "")
        pooled = f" key-pool={args.key_pool}" if args.key_pool else ""
        print(f"arch={args.arch} watermark={args.watermark} "
              f"continuous batching{paged}{pooled}: {len(results)} "
              f"requests over {args.batch} slots"
              + (" [overlap]" if args.overlap else ""))
        print(f"AATPS={acc / max(alive, 1):.3f} tokens={tot} "
              f"alive-slot-steps={alive}")
        if "ttft_mean_s" in stats:
            gap = (f" gap mean={stats['gap_mean_s'] * 1e3:.1f}ms "
                   f"p95={stats['gap_p95_s'] * 1e3:.1f}ms"
                   if "gap_mean_s" in stats else "")
            print(f"TTFT mean={stats['ttft_mean_s'] * 1e3:.1f}ms{gap} "
                  "(first-serve wall clock, compile included)")
        if "prefix_hits" in stats:
            print(f"prefix cache: hits={stats['prefix_hits']:.0f} "
                  f"misses={stats['prefix_misses']:.0f} "
                  f"pages-saved={stats['prefix_pages_saved']:.0f} "
                  f"evictions={stats['prefix_evictions']:.0f} "
                  f"(entries={stats['prefix_entries']:.0f}, "
                  f"pages held={stats['prefix_pages']:.0f})")
        for r in results[:8]:
            tail = " eos" if r.eos else ""
            tier = f" tier={r.tier}" if r.tier else ""
            print(f"  req {r.uid}: {r.length} tokens{tail} "
                  f"key={r.key_fingerprint} gamma={r.strength:g}{tier} | "
                  + synthetic.decode_bytes(r.tokens)[:40].decode(
                      "latin1"))
        return

    res = E.generate(t_params, d_params, tcfg, dcfg, scfg, prompts,
                     n_tokens=args.tokens, key=key, extras=extras,
                     mesh=mesh)
    print(f"arch={args.arch} watermark={args.watermark} "
          f"accept={args.accept} K={args.k}")
    print(f"AATPS={res.aatps:.3f} tokens/step={res.tokens_per_step:.3f} "
          f"steps={res.n_steps} tokens={int(res.lengths.sum())}")
    print("sample bytes:", synthetic.decode_bytes(
        res.tokens[0, :args.tokens])[:60])


if __name__ == "__main__":
    main()
