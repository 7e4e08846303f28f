"""Kernel micro-benchmarks: us/call of the Pallas kernels (interpret mode
on CPU — structural validation; wall-times are NOT TPU projections) and
allclose deltas vs the jnp oracles."""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from repro.kernels import ops, ref

ART = common.ART


def run(verbose=True):
    rows = []
    key = jax.random.key(0)
    for B, V in [(8, 4096), (4, 32000)]:
        probs = jax.nn.softmax(jax.random.normal(key, (B, V)))
        seeds = jax.random.bits(key, (B,), dtype=jnp.uint32)
        t, (tok_k, _) = common.timer(
            lambda: ops.gumbel_argmax(probs, seeds, interpret=True))
        t_ref, (tok_r, _) = common.timer(
            lambda: jax.jit(ref.gumbel_argmax_ref)(probs, seeds))
        match = bool(np.array_equal(np.asarray(tok_k), np.asarray(tok_r)))
        rows.append({"kernel": "gumbel_argmax", "B": B, "V": V,
                     "us_per_call": round(t * 1e6, 1),
                     "ref_us": round(t_ref * 1e6, 1), "exact": match})
        t, (d_k,) = common.timer(
            lambda: (ops.tournament(probs, seeds, m=30, interpret=True),))
        t_ref, (d_r,) = common.timer(
            lambda: (jax.jit(lambda p, s: ref.tournament_ref(p, s, m=30))(
                probs, seeds),))
        match = bool(np.allclose(np.asarray(d_k), np.asarray(d_r),
                                 rtol=1e-5, atol=1e-6))
        rows.append({"kernel": "tournament_m30", "B": B, "V": V,
                     "us_per_call": round(t * 1e6, 1),
                     "ref_us": round(t_ref * 1e6, 1), "exact": match})
    B, K, V = 8, 4, 4096
    p = jax.nn.softmax(jax.random.normal(jax.random.key(1), (B, K, V)))
    q = jax.nn.softmax(jax.random.normal(jax.random.key(2), (B, K, V)))
    toks = jax.random.randint(jax.random.key(3), (B, K), 0, V)
    u = jax.random.uniform(jax.random.key(4), (B, K))
    seeds = jax.random.bits(jax.random.key(5), (B, K), dtype=jnp.uint32)
    t, outs_k = common.timer(
        lambda: ops.spec_verify(p, q, toks, u, seeds, interpret=True))
    t_ref, outs_r = common.timer(
        lambda: jax.jit(ref.spec_verify_ref)(p, q, toks, u, seeds))
    match = all(np.allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
                for a, b in zip(outs_k, outs_r))
    rows.append({"kernel": "spec_verify", "B": B, "V": V,
                 "us_per_call": round(t * 1e6, 1),
                 "ref_us": round(t_ref * 1e6, 1), "exact": match})

    # fused watermarked tail (verify + residual/bonus race + seen switch);
    # per-row key words + ctx hashes — seeds are chained in-kernel
    pw = jax.nn.softmax(jax.random.normal(jax.random.key(6), (B, K + 1, V)))
    keyr = jax.random.bits(jax.random.key(7), (B,), dtype=jnp.uint32)
    ctxh = jax.random.bits(jax.random.key(8), (B, K + 1), dtype=jnp.uint32)
    seen = (jax.random.uniform(jax.random.key(9), (B, K + 1)) < 0.2)
    # interpret=True: measure the staged Pallas program, not the CPU
    # fast-path mirror (which IS the ref)
    t, outs_k = common.timer(
        lambda: ops.spec_verify_wm(pw, q, toks, u, keyr, ctxh, seen,
                                   interpret=True))
    t_ref, outs_r = common.timer(
        lambda: jax.jit(ref.spec_verify_wm_ref,
                        static_argnames=("streams",))(
            pw, q, toks, u, keyr, ctxh, seen,
            streams=ops.DEFAULT_STREAMS))
    match = all(np.allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
                for a, b in zip(outs_k, outs_r))
    rows.append({"kernel": "spec_verify_wm", "B": B, "V": V,
                 "us_per_call": round(t * 1e6, 1),
                 "ref_us": round(t_ref * 1e6, 1), "exact": match})
    if verbose:
        for r in rows:
            print(f"kernels,{r['kernel']},B={r['B']},V={r['V']},"
                  f"{r['us_per_call']}us,ref={r['ref_us']}us")
    os.makedirs(ART, exist_ok=True)
    with open(os.path.join(ART, "kernels_bench.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return rows


if __name__ == "__main__":
    run()
