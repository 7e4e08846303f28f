#!/usr/bin/env python3
"""Chip smoke test: the serving engine's main path on TPU at published widths.

With no option (one chip) it serves yi-6b — 32 layers, d_model 4096, GQA
32/4, head_dim 128, vocab 64000 — with its ``configs.draft_for`` dense
companion draft, both in bf16 with weights drawn from ``--seed`` (real
checkpoints are not part of the repository), through
``engine.serve_requests`` and the paged KV pool.  Phases:

  1. device: platform, device kind and count; anything but a TPU fails;
  2. params + compile: bf16 params built on the device; the spec step
     compiled, and its compiled text must hold the Mosaic verify-tail and
     paged-attention kernels;
  3. serve: 16 requests (prompts of 64-512 tokens, 64 new tokens each,
     8 slots, K=4) once under gumbel and once under synthid; every
     request must drain to its n_tokens with valid token ids;
  4. kernels vs their jnp mirrors on the chip at these widths: the verify
     tail's n_acc/accepted must be equal; paged attention must agree
     within a bf16 tolerance;
  5. detection: served text scored under the serving key must separate
     from the same text under a wrong key (gumbel and synthid scores);
  6. served vs solo ``generate()`` token agreement for a few requests,
     reported as a measured fact.

``--chips 4`` runs only the sharded path instead: the paper's Gemma-7B
target with its Gemma-2B draft (too large for one chip in bf16) on a
(data=1, model=4) mesh, served vs solo ``generate()`` on the same mesh,
and key detection.

Run from the repository root (one process owns the chip; no child
process touches JAX):

    python chip_smoke.py [--seed N] [--chips 4]

Any failed phase exits non-zero.  The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

K = 4                     # drafted tokens per spec step
BATCH = 8                 # serving slots
N_TOKENS = 64             # new tokens per request
PAGE_SIZE = 16
PREFILL_CHUNK = 128
TEMPERATURE = 0.7
# kernel-vs-mirror tolerance for paged attention at bf16: the mirror
# rounds its scores and probabilities to bf16 (dense einsum path), the
# kernel accumulates in f32 — both land within a few bf16 ulps of O(1)
# outputs
PAGED_ATOL = 2e-2
PAGED_RTOL = 2e-2


class PhaseFailed(RuntimeError):
    pass


def check(cond, msg):
    if not cond:
        raise PhaseFailed(msg)


def log(msg):
    print(msg, flush=True)


def make_requests(rng, n, vocab, lo, hi, n_tokens):
    """``n`` requests with prompt lengths uniform in [lo, hi] and token ids
    in [1, vocab), all from ``rng``."""
    import numpy as np
    lens = rng.integers(lo, hi + 1, size=n)
    return [{"prompt": rng.integers(1, vocab, size=int(s)).astype(np.int32),
             "n_tokens": n_tokens} for s in lens]


def pool_pages(requests, batch, page_size):
    """Pages for ``batch`` slots each holding the longest request at its
    worst-case extent (the scheduler's ``max_seq``), plus the null page."""
    longest = max(len(r["prompt"]) for r in requests)
    n_new = max(r["n_tokens"] for r in requests)
    max_seq = longest + 1 + (K + 1) * n_new + 2
    return batch * -(-max_seq // page_size) + 1


def init_params(cfg, seed, shardings=None):
    """bf16 params generated on the device (sharded there when
    ``shardings`` is given) — never a float32 host copy."""
    import jax
    import jax.numpy as jnp

    from repro.models import model as M
    init = jax.jit(M.init_params, static_argnums=(1, 2),
                   out_shardings=shardings)
    params = init(jax.random.key(seed), cfg, jnp.bfloat16)
    return jax.block_until_ready(params)


def serve(t_params, d_params, tcfg, dcfg, watermark, requests, key_word,
          num_pages, mesh=None):
    """One ``serve_requests`` run over the paged pool; checks that every
    request drained to its n_tokens with valid token ids."""
    import numpy as np

    from repro.serve import engine as E
    from repro.serve.config import ServeConfig
    scfg = E.SpecConfig(K=K, watermark=watermark, temperature=TEMPERATURE)
    cfg = ServeConfig(batch=BATCH, key=key_word, page_size=PAGE_SIZE,
                      num_pages=num_pages, prefill_chunk=PREFILL_CHUNK,
                      mesh=mesh)
    stats: dict = {}
    t0 = time.perf_counter()
    results = E.serve_requests(t_params, d_params, tcfg, dcfg, scfg,
                               requests, config=cfg, stats_out=stats)
    wall = time.perf_counter() - t0
    check(len(results) == len(requests),
          f"{watermark}: {len(results)} results for {len(requests)} "
          "requests")
    for r, req in zip(sorted(results, key=lambda r: r.uid), requests):
        # a slot stops at the first step that reaches its target, so it
        # may commit up to K more tokens than asked
        check(req["n_tokens"] <= r.length <= req["n_tokens"] + K,
              f"{watermark}: request {r.uid} drained {r.length} tokens "
              f"for a target of {req['n_tokens']}")
        toks = np.asarray(r.tokens)
        check(toks.shape == (r.length,)
              and toks.min() >= 0 and toks.max() < tcfg.vocab,
              f"{watermark}: request {r.uid} has token ids outside "
              f"[0, {tcfg.vocab})")
    log(f"  serve[{watermark}]: {len(results)} requests drained, "
        f"alive slot-steps={stats['alive_slot_steps']:.0f}, "
        f"accepted tokens/step={stats['aatps']:.4f}, "
        f"emitted tokens/step={stats['tokens_per_step']:.4f}, "
        f"pages peak={stats['pages_peak']:.0f}/{num_pages}, "
        f"wall={wall:.1f}s (one-off smoke timing, compile included)")
    return scfg, sorted(results, key=lambda r: r.uid)


def detect(scfg, results, key_word, vocab):
    """Score every served text under the serving key and a wrong key; the
    median serving-key score must clear every wrong-key score."""
    import numpy as np

    from repro.core.detection.multikey import score_texts_by_keys
    from repro.serve import engine as E
    wrong = key_word ^ 0x5BD1E995
    rep = score_texts_by_keys(results, [key_word, wrong],
                              E.make_decoder(scfg), vocab)
    right_z, wrong_z = rep.scores[:, 0], rep.scores[:, 1]
    log(f"  detect[{scfg.watermark}]: serving-key z min/median "
        f"{right_z.min():.2f}/{np.median(right_z):.2f}, wrong-key z "
        f"median/max {np.median(wrong_z):.2f}/{wrong_z.max():.2f}")
    check(np.median(right_z) > wrong_z.max(),
          f"{scfg.watermark}: serving-key scores do not separate from "
          "wrong-key scores")


def served_vs_solo(t_params, d_params, tcfg, dcfg, scfg, requests, results,
                   key_word, n, mesh=None):
    """Token agreement of served requests with a solo ``generate()`` of
    the same prompt and key (dense cache, batch 1) — reported, not
    asserted: bit-exactness holds on the CPU mirrors, and the chip's
    kernels are a different operation order."""
    import jax.numpy as jnp
    import numpy as np

    from repro.serve import engine as E
    for req, r in list(zip(requests, results))[:n]:
        solo = E.generate(t_params, d_params, tcfg, dcfg, scfg,
                          jnp.asarray(req["prompt"])[None],
                          n_tokens=req["n_tokens"], key=key_word, mesh=mesh)
        a = np.asarray(r.tokens)[:req["n_tokens"]]
        b = np.asarray(solo.tokens[0, :req["n_tokens"]])
        diff = np.flatnonzero(a != b)
        lead = int(diff[0]) if diff.size else len(a)
        log(f"  served-vs-solo[{scfg.watermark}] request {r.uid}: "
            f"{int((a == b).sum())}/{len(a)} tokens equal, first "
            f"{lead} identical" + (" (bit-exact)" if lead == len(a) else ""))


def compiled_kernels(tcfg, dcfg, t_params, d_params, num_pages, max_pages):
    """Compile one spec step over a paged state (shapes only); returns
    (seconds, names of the Mosaic kernels in the compiled program, number
    of TPU custom calls)."""
    import jax
    import jax.numpy as jnp

    from repro.serve import engine as E
    scfg = E.SpecConfig(K=K, watermark="gumbel", temperature=TEMPERATURE)
    state = jax.eval_shape(lambda: E.init_empty_paged_state(
        tcfg, dcfg, scfg, BATCH, num_pages=num_pages, page_size=PAGE_SIZE,
        max_pages=max_pages, cache_dtype=t_params["embed"].dtype))
    live = jax.ShapeDtypeStruct((BATCH,), jnp.bool_)
    t0 = time.perf_counter()
    compiled = E.jitted_spec_step(tcfg, dcfg, scfg).lower(
        t_params, d_params, state, live).compile()
    secs = time.perf_counter() - t0
    lines = [ln for ln in compiled.as_text().splitlines()
             if "tpu_custom_call" in ln]
    names = {nm for nm in ("spec_verify_wm", "paged_attention")
             if any(nm in ln for ln in lines)}
    return secs, names, len(lines)


def kernels_vs_mirrors(seed, tcfg):
    """The Mosaic kernels against their jnp mirrors on the chip, on the
    same inputs, at the served widths."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.watermark.base import FusedTail
    from repro.kernels import ops, ref
    from repro.kernels.paged_attention import paged_decode_attention
    V = tcfg.vocab
    ks = jax.random.split(jax.random.key(seed + 100), 12)
    p = jax.nn.softmax(jax.random.normal(ks[0], (BATCH, K + 1, V)) * 2)
    q = jax.nn.softmax(jax.random.normal(ks[1], (BATCH, K, V)) * 2)
    # half the drafts are the draft's own argmax, so prefixes vary
    toks = jnp.where(jax.random.bernoulli(ks[2], 0.5, (BATCH, K)),
                     jnp.argmax(q, -1),
                     jax.random.randint(ks[3], (BATCH, K), 0, V))
    u = jax.random.uniform(ks[4], (BATCH, K))
    keys = jax.random.bits(ks[5], (BATCH,), jnp.uint32)
    ctx = jax.random.bits(ks[6], (BATCH, K + 1), jnp.uint32)
    seen = jax.random.uniform(ks[7], (BATCH, K + 1)) < 0.2
    live = jnp.ones((BATCH,), jnp.int32).at[-1].set(0)
    args = (p, q, toks, u, keys, ctx, seen, live)
    mirror = jax.jit(ref.spec_verify_wm_ref,
                     static_argnames=("streams", "tail"))
    for tail in (FusedTail("race", stat_dim=1),
                 FusedTail("tournament", m=30, stat_dim=30)):
        got = ops.spec_verify_wm(*args, tail=tail)
        want = mirror(*args, streams=ops.DEFAULT_STREAMS, tail=tail)
        got, want = jax.device_get((got, want))
        check(np.array_equal(got[0], want[0])
              and np.array_equal(got[1], want[1]),
              f"verify tail ({tail.kind}): n_acc/accepted differ from the "
              "mirror")
        agree = float(np.mean(got[2] == want[2]))
        log(f"  verify tail[{tail.kind}] B={BATCH} K={K} V={V}: n_acc and "
            f"accepted equal to the mirror (n_acc={got[0].tolist()}); "
            f"emitted-token agreement {agree:.3f}")

    # paged attention at the target's widths over a bf16 pool
    Hkv, H, hd = tcfg.n_kv_heads, tcfg.n_heads, tcfg.resolved_head_dim
    max_pages, n_pool = 40, 512
    rng = np.random.default_rng(seed)
    qa = jax.random.normal(ks[8], (BATCH, K + 1, H, hd), jnp.bfloat16)
    kp = jax.random.normal(ks[9], (n_pool, Hkv, PAGE_SIZE, hd),
                           jnp.bfloat16)
    vp = jax.random.normal(ks[10], (n_pool, Hkv, PAGE_SIZE, hd),
                           jnp.bfloat16)
    table = jnp.asarray(rng.permutation(np.arange(1, n_pool))
                        [:BATCH * max_pages].reshape(BATCH, max_pages),
                        jnp.int32)
    pos = jnp.asarray(rng.integers(1, max_pages * PAGE_SIZE - K,
                                   size=BATCH), jnp.int32)
    got = paged_decode_attention(qa, kp, vp, table, pos)
    want = jax.jit(ref.paged_attention_ref)(qa, kp, vp, table, pos)
    exact = jax.jit(ref.paged_attention_ref)(
        qa.astype(jnp.float32), kp.astype(jnp.float32),
        vp.astype(jnp.float32), table, pos)
    got, want, exact = (np.asarray(x, np.float32) for x in
                        jax.device_get((got, want, exact)))
    err = np.abs(got - want)
    rel = float(err.max() / np.abs(want).max())
    log(f"  paged attention B={BATCH} Sq={K + 1} H={H} Hkv={Hkv} hd={hd} "
        f"bf16: max abs err vs mirror {err.max():.3e} (rel {rel:.3e}); "
        f"vs f32 reference: kernel {np.abs(got - exact).max():.3e}, "
        f"mirror {np.abs(want - exact).max():.3e}")
    check(np.allclose(got, want, atol=PAGED_ATOL, rtol=PAGED_RTOL),
          f"paged attention differs from the mirror beyond atol="
          f"{PAGED_ATOL}, rtol={PAGED_RTOL}")


def run_one_chip(seed):
    import numpy as np

    from repro import configs
    tcfg = configs.get_config("yi-6b")
    dcfg = configs.draft_for(tcfg)
    rng = np.random.default_rng(seed)
    requests = make_requests(rng, 16, tcfg.vocab, 64, 512, N_TOKENS)
    num_pages = pool_pages(requests, BATCH, PAGE_SIZE)
    key_word = int(rng.integers(1, 2**31))

    log("phase 2: params + compile")
    t0 = time.perf_counter()
    t_params = init_params(tcfg, seed)
    d_params = init_params(dcfg, seed + 1)
    log(f"  params: target {tcfg.name} + draft {dcfg.name} in bf16, "
        f"{time.perf_counter() - t0:.1f}s")
    secs, names, n_calls = compiled_kernels(
        tcfg, dcfg, t_params, d_params, num_pages,
        max_pages=-(-(512 + 1 + (K + 1) * N_TOKENS + 2) // PAGE_SIZE))
    log(f"  compile seconds (spec step): {secs:.1f}; tpu_custom_calls: "
        f"{n_calls}; kernels: {sorted(names)}")
    check(names == {"spec_verify_wm", "paged_attention"},
          f"compiled spec step lacks Mosaic kernels: has {sorted(names)}")

    log("phase 3: serve")
    served = {}
    for wm in ("gumbel", "synthid"):
        served[wm] = serve(t_params, d_params, tcfg, dcfg, wm, requests,
                           key_word, num_pages)

    log("phase 4: kernels vs mirrors")
    kernels_vs_mirrors(seed, tcfg)

    log("phase 5: detection")
    for wm, (scfg, results) in served.items():
        detect(scfg, results, key_word, tcfg.vocab)

    log("phase 6: served vs solo generate()")
    for wm, (scfg, results) in served.items():
        served_vs_solo(t_params, d_params, tcfg, dcfg, scfg, requests,
                       results, key_word, n=2)


def run_four_chips(seed):
    import numpy as np

    from repro.configs import paper_models
    from repro.launch.mesh import make_host_mesh
    from repro.models import model as M
    from repro.sharding import rules as SHR
    tcfg, dcfg = paper_models.GEMMA_7B, paper_models.GEMMA_2B
    mesh = make_host_mesh(data=1, model=4)
    rng = np.random.default_rng(seed)
    requests = make_requests(rng, 8, tcfg.vocab, 64, 512, N_TOKENS)
    num_pages = pool_pages(requests, BATCH, PAGE_SIZE)
    key_word = int(rng.integers(1, 2**31))

    log(f"phase 2: params on mesh {dict(mesh.shape)}")
    t0 = time.perf_counter()
    t_params = init_params(tcfg, seed, SHR.param_shardings(
        M.abstract_params(tcfg), mesh))
    d_params = init_params(dcfg, seed + 1, SHR.param_shardings(
        M.abstract_params(dcfg), mesh))
    log(f"  params: target {tcfg.name} + draft {dcfg.name} in bf16, "
        f"sharded, {time.perf_counter() - t0:.1f}s")

    log("phase 3: serve (sharded)")
    scfg, results = serve(t_params, d_params, tcfg, dcfg, "gumbel",
                          requests, key_word, num_pages, mesh=mesh)
    log("phase 5: detection")
    detect(scfg, results, key_word, tcfg.vocab)
    log("phase 6: served vs solo generate() on the same mesh")
    served_vs_solo(t_params, d_params, tcfg, dcfg, scfg, requests, results,
                   key_word, n=2, mesh=mesh)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke.py: no repro package under {src}; run it from "
              "a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    from repro.launch import compile_cache
    cache_dir = compile_cache.enable()

    import jax
    devices = jax.devices()
    dev = devices[0]
    log("phase 1: device")
    log(f"  platform={dev.platform} device_kind={dev.device_kind} "
        f"device_count={len(devices)} compile_cache={cache_dir}")
    if dev.platform != "tpu":
        print(f"chip_smoke.py: no TPU (platform={dev.platform}); this "
              "smoke runs on the chip only", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke.py: --chips {args.chips} but {len(devices)} "
              "devices", file=sys.stderr)
        return 1
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            run_four_chips(args.seed)
        else:
            run_one_chip(args.seed)
    except PhaseFailed as e:
        print(f"chip_smoke.py: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
