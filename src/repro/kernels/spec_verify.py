"""Fused speculative verification kernel (Alg. 1 accept/reject + residual).

Per sequence row, given the K target/draft probability rows, the drafted
tokens and the pseudorandom acceptance coins u = G(zeta^R):

  1. gathers p_s(w_s), q_s(w_s) via masked sums (TPU-friendly one-hot dot,
     no scalar gathers),
  2. computes the prefix-acceptance  n_acc = |{s : all u_<s ok and u_s <
     min(1, p/q)}|,
  3. for the first rejected slot, samples the *watermarked* residual token
     argmax_w log(U_w)/(p_w - q_w)_+  with in-kernel PRF uniforms —
     the Gumbel-max race is scale-invariant, so the residual needs no
     normalization pass.

Everything after the two model calls of a speculative step fuses into one
VMEM-resident pass over the (K, V) probability block.

``spec_verify_wm`` extends this into the full watermarked tail of Alg. 1,
with a scheme-pluggable emitted-token branch (``FusedTail``):

- kind="race" (Gumbel-max / plain): one watermarked Gumbel race over the
  residual  argmax_w log(U_w)/(p_w − q_w)_+  at the first rejected slot,
  or over the bonus row p_K when all K drafts are accepted;
- kind="tournament" (SynthID): the residual/bonus row is normalized and
  driven through the m-round tournament operator *in VMEM* (reusing the
  ``tournament_kernel`` round body and in-kernel g-bit PRF), then the
  emitted token is drawn by a counter-PRF race (finite m) or argmax
  (degenerate m→∞ limit), and its m g-bit detection statistics are
  emitted alongside.

Either way the PRF stream switches in-kernel: repeated contexts (Hu et
al.'s ``seen`` mask) draw with the non-watermark stream seed instead of
the ζ^T one.  Exactly one (V,)-sized race runs per row, replacing the
engine's former O(K·V)-per-row residual materialization — and for
SynthID the m tournament rounds touch HBM once (one read of the
residual row) instead of materializing m (V,) vectors.

Both kernels are written against the *local* batch: on a mesh, the
``ops.spec_verify_wm`` wrapper shard_maps the call over the dp axes, so
``grid=(B,)`` here spans the per-shard batch rows — every row is
independent, so the sharded program stays collective-free.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gumbel_argmax import _seed_chain, _uniform
from repro.kernels.tournament import _gbit


def _kernel(p_ref, q_ref, tok_ref, u_ref, seed_ref,
            nacc_ref, acc_ref, rtok_ref, ru_ref, *, K: int, vocab: int):
    p = p_ref[0].astype(jnp.float32)       # (K, Vp)
    q = q_ref[0].astype(jnp.float32)       # (K, Vp)
    toks = tok_ref[0]                      # (K,)
    u = u_ref[0].astype(jnp.float32)       # (K,)
    seeds = seed_ref[0].astype(jnp.uint32)  # (K,)
    kv, vp = p.shape
    w = jax.lax.broadcasted_iota(jnp.int32, (kv, vp), 1)
    onehot = (w == toks[:, None]).astype(jnp.float32)
    p_tok = jnp.sum(p * onehot, axis=-1)   # (K,)
    q_tok = jnp.sum(q * onehot, axis=-1)
    a = jnp.minimum(1.0, p_tok / jnp.maximum(q_tok, 1e-30))
    ok = (u < a).astype(jnp.int32)
    prefix = jnp.cumprod(ok)
    n_acc = jnp.sum(prefix)
    acc_ref[0] = prefix
    nacc_ref[0] = n_acc.astype(jnp.int32)[None]

    # residual sampling at slot min(n_acc, K-1): Gumbel race over (p-q)_+
    slot = jnp.minimum(n_acc, K - 1)
    sel = (jax.lax.broadcasted_iota(jnp.int32, (kv, 1), 0)
           == slot).astype(jnp.float32)
    p_s = jnp.sum(p * sel, axis=0)         # (Vp,)
    q_s = jnp.sum(q * sel, axis=0)
    seed_s = jnp.sum(seeds * (jax.lax.iota(jnp.int32, kv) == slot
                              ).astype(jnp.uint32))
    r = jnp.maximum(p_s - q_s, 0.0)
    wv = jax.lax.iota(jnp.uint32, vp)
    uv = _uniform(seed_s, wv)
    score = jnp.log(uv) / jnp.maximum(r, 1e-30)
    score = jnp.where((r > 0) & (wv < vocab), score, -jnp.inf)
    rtok = jnp.argmax(score).astype(jnp.int32)
    rtok_ref[0] = rtok[None]
    ru_ref[0] = jnp.sum(uv * (wv == rtok.astype(jnp.uint32))
                        .astype(jnp.float32))[None]


def spec_verify_kernel(p, q, draft_tokens, u, resid_seeds, *,
                       interpret: bool = False):
    """p, q: (B, K, V); draft_tokens: (B, K) int32; u: (B, K) f32 coins;
    resid_seeds: (B, K) uint32 (zeta^T residual stream seeds).

    Returns (n_acc (B,), accepted (B, K), resid_tok (B,), resid_u (B,))."""
    B, K, V = p.shape
    vp = -(-V // 128) * 128
    pp = jnp.zeros((B, K, vp), p.dtype).at[:, :, :V].set(p)
    qp = jnp.zeros((B, K, vp), q.dtype).at[:, :, :V].set(q)
    outs = pl.pallas_call(
        functools.partial(_kernel, K=K, vocab=V),
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, K, vp), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, K, vp), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, K), lambda i: (i, 0)),
            pl.BlockSpec((1, K), lambda i: (i, 0)),
            pl.BlockSpec((1, K), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, K), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
            jax.ShapeDtypeStruct((B, K), jnp.int32),
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
            jax.ShapeDtypeStruct((B, 1), jnp.float32),
        ],
        interpret=interpret,
    )(pp, qp, draft_tokens, u, resid_seeds.astype(jnp.uint32))
    n_acc, acc, rtok, ru = outs
    return n_acc[:, 0], acc, rtok[:, 0], ru[:, 0]


_LANES = 128


def _lanes(n: int = _LANES):
    """(1, n) int32 lane iota — Mosaic lowers 2D iotas only."""
    return jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)


def _first_argmax(score):
    """``jnp.argmax`` of a (1, n) row (first maximal lane) as a (1, 1)
    int32, written as max + masked min — reductions Mosaic lowers."""
    n = score.shape[-1]
    best = jnp.max(score, axis=-1, keepdims=True)
    return jnp.min(jnp.where(score == best, _lanes(n), n), axis=-1,
                   keepdims=True)


def _pick(onehot, row):
    """Sum of ``row`` over the lanes where ``onehot`` holds: the value at
    the single selected lane (exact — every other term is +0)."""
    return jnp.sum(jnp.where(onehot, row, 0.0), axis=-1, keepdims=True)


def _wm_vmem_bytes(K: int, vp: int, p_dtype, q_dtype) -> int:
    """Scoped-VMEM budget of one ``_wm_kernel`` grid step: the
    double-buffered (K+1, Vp) / (K, Vp) probability blocks (second-minor
    dim padded to the dtype's sublane tile), 48 (1, Vp) f32 working rows
    (the v5e compiler kept up to ~30 live at V = 256k inside the served
    step) and 2 MiB of slack.  That exceeds the default scoped limit at
    large vocabularies, so the kernel asks for it explicitly."""
    def block(rows, dtype):
        itemsize = jnp.dtype(dtype).itemsize
        sub = 8 * (4 // itemsize)
        return -(-rows // sub) * sub * vp * itemsize
    return (2 * (block(K + 1, p_dtype) + block(K, q_dtype))
            + 48 * vp * 4 + (2 << 20))


def _wm_kernel(tok_ref, u_ref, key_ref, ctx_ref, seen_ref, live_ref,
               p_ref, q_ref, nacc_ref, acc_ref, etok_ref, estat_ref,
               *, K: int, vocab: int, kind: str, m: int, degenerate: bool,
               wm_stream: int, plain_resid: int, plain_bonus: int,
               draw_stream: int):
    """One grid step = one sequence row ``i``.  The per-row scalars
    (draft tokens, coins, key word, context hashes, seen and live flags)
    are SMEM scalars indexed by the row; the probability rows are the
    row's (K+1, Vp) / (K, Vp) VMEM blocks; every output is a lane-dense
    (1, 128)-padded row sliced by the wrapper."""
    i = pl.program_id(0)
    lane = _lanes()
    # Zero-init so non-live (drained continuous-batching slot) rows emit
    # defined outputs; the whole verification/race body is then predicated
    # off for them — a drained row costs no gather/race work on TPU.
    nacc_ref[0] = jnp.zeros((1, _LANES), jnp.int32)
    acc_ref[0] = jnp.zeros((1, _LANES), jnp.int32)
    etok_ref[0] = jnp.zeros((1, _LANES), jnp.int32)
    estat_ref[0] = jnp.zeros(estat_ref.shape[1:], jnp.float32)

    @pl.when(live_ref[i] != 0)
    def _():
        vp = q_ref.shape[-1]
        wl = _lanes(vp)
        wv = wl.astype(jnp.uint32)
        rows_p = [p_ref[0, k:k + 1, :].astype(jnp.float32)
                  for k in range(K + 1)]     # slot K = bonus dist
        rows_q = [q_ref[0, k:k + 1, :].astype(jnp.float32)
                  for k in range(K)]
        # prefix acceptance: slot k is accepted iff every coin up to k
        # passes u_s < min(1, p/q) — a running AND over the K slots
        alive = jnp.ones((1, 1), jnp.bool_)
        n_acc = jnp.zeros((1, 1), jnp.int32)
        for k in range(K):
            hit = wl == tok_ref[i, k]
            a = jnp.minimum(1.0, _pick(hit, rows_p[k])
                            / jnp.maximum(_pick(hit, rows_q[k]), 1e-30))
            alive = alive & (u_ref[i, k] < a)
            n_acc = n_acc + alive.astype(jnp.int32)
        acc_ref[0] = (lane < n_acc).astype(jnp.int32)
        nacc_ref[0] = jnp.broadcast_to(n_acc, (1, _LANES))

        # the single emitted extra token comes from slot n_acc in [0, K]:
        # for n_acc < K its base row is (p − q)_+ (first-rejection
        # residual); for n_acc == K no q row is selected, so r == p_K
        # (bonus).
        slot = n_acc
        p_s = jnp.zeros((1, vp), jnp.float32)
        q_s = jnp.zeros((1, vp), jnp.float32)
        ctx_s = jnp.zeros((1, 1), jnp.uint32)
        seen_s = jnp.zeros((1, 1), jnp.int32)
        for k in range(K + 1):
            at = slot == k
            p_s = jnp.where(at, rows_p[k], p_s)
            if k < K:
                q_s = jnp.where(at, rows_q[k], q_s)
            ctx_s = jnp.where(at, ctx_ref[i, k], ctx_s)
            seen_s = jnp.where(at, seen_ref[i, k], seen_s)
        # per-slot PRF seeds, re-derived from the row's key word: chain
        # stream -> context.  The plain stream differs for the bonus slot
        # (slot == K) vs a residual slot.
        key = key_ref[i]
        pl_stream = jnp.where(slot == K, jnp.uint32(plain_bonus),
                              jnp.uint32(plain_resid))
        wm_s = _seed_chain(_seed_chain(key, jnp.uint32(wm_stream)), ctx_s)
        pl_s = _seed_chain(_seed_chain(key, pl_stream), ctx_s)
        r = jnp.maximum(p_s - q_s, 0.0)

        if kind == "race":
            # Gumbel-max race over the raw row (scale-invariant, so the
            # residual needs no normalization pass); repeated contexts
            # switch to the non-watermark stream seed.
            seed_s = jnp.where(seen_s != 0, pl_s, wm_s)
            uv = _uniform(seed_s, wv)
            score = jnp.log(uv) / jnp.maximum(r, 1e-30)
            score = jnp.where((r > 0) & (wl < vocab), score, -jnp.inf)
            etok = _first_argmax(score)
            estat_ref[0] = jnp.broadcast_to(_pick(wl == etok, uv),
                                            estat_ref.shape[1:])
        else:                       # kind == "tournament" (SynthID)
            # the tournament operator is not scale-invariant: normalize
            # the row at the padded-lane extent (the canon every jnp
            # mirror and the host decoder follow), then run the m rounds
            # VMEM-resident with the tournament_kernel round body.
            dw_s = _seed_chain(_seed_chain(key, jnp.uint32(draw_stream)),
                               ctx_s)
            z = jnp.sum(r, axis=-1, keepdims=True)
            rn = r / jnp.maximum(z, 1e-30)             # (1, Vp)

            def round_body(j, pz):
                g = _gbit(wm_s, wv + jnp.uint32(vocab) * j.astype(
                    jnp.uint32))
                mass_one = jnp.sum(pz * g, axis=-1, keepdims=True)
                return pz * (1.0 + g - mass_one)

            pz = jax.lax.fori_loop(0, m, round_body, rn)
            # repeated contexts draw from the *raw* (un-tournamented) row
            # with the plain seed; the finite-m tournament draw is a
            # counter-PRF race, the m→∞ limit an argmax
            race_dist = jnp.where(seen_s != 0, rn, pz)
            race_seed = jnp.where(seen_s != 0, pl_s, dw_s)
            uv = _uniform(race_seed, wv)
            score = jnp.log(uv) / jnp.maximum(race_dist, 1e-30)
            score = jnp.where((race_dist > 0) & (wl < vocab), score,
                              -jnp.inf)
            etok = _first_argmax(score)
            if degenerate:
                arg_tok = _first_argmax(jnp.where(wl < vocab, pz, -jnp.inf))
                etok = jnp.where(seen_s != 0, etok, arg_tok)
            # m g-bit detection statistics of the emitted token under the
            # zeta^T g-seed (counter tok + V*l — matches recover_stats);
            # lanes >= m are sliced off by the wrapper
            li = _lanes(estat_ref.shape[-1]).astype(jnp.uint32)
            estat_ref[0] = _gbit(wm_s, etok.astype(jnp.uint32)
                                 + jnp.uint32(vocab) * li)
        etok_ref[0] = jnp.broadcast_to(etok, (1, _LANES))


def spec_verify_wm_kernel(p, q, draft_tokens, u, keys, ctx_hashes,
                          seen, live=None, *, streams, tail=None,
                          interpret: bool = False):
    """Fused watermarked verification tail of Alg. 1 (accept/reject +
    residual-or-bonus sampling) — one VMEM pass per sequence row.

    p: (B, K+1, V) target probs for the K verified slots plus the bonus
    slot; q: (B, K, V) draft probs; draft_tokens: (B, K) int32; u: (B, K)
    acceptance coins; keys: (B,) uint32 per-row watermark key words;
    ctx_hashes: (B, K+1) uint32 per-slot context hashes; seen: (B, K+1)
    repeated-context mask (nonzero -> fall back to the plain stream).

    ``streams`` (static tuple of ints ``(wm_stream, plain_resid,
    plain_bonus, draw_stream)``) names the PRF streams; the per-slot seeds
    are re-derived *in VMEM* from the key row via the two-link counter
    chain (``prf.wm_seed`` mirror) — no host-derived seed tensors cross
    HBM, and mixed-key batches cost nothing extra.

    ``tail`` (a ``watermark.base.FusedTail``, default the Gumbel race)
    selects the scheme's emitted-token branch; kind="tournament" tails
    additionally use ``draw_stream`` for the finite-m categorical draw
    (ignored by races and degenerate tournaments).

    ``live`` (optional, (B,) bool/int): slot mask for continuous batching —
    rows with live == 0 (drained serving slots) skip the whole verification
    body under ``pl.when`` and return all-zero outputs.  None = all rows
    live.

    Returns (n_acc (B,), accepted (B, K) int32, extra_tok (B,), extra_stat)
    where extra_tok is the emitted slot-n_acc token (residual on first
    rejection, bonus when all accepted) and extra_stat its detection
    statistic — the PRF race uniform (B,) for kind="race", the m g-bits
    (B, m) of the emitted token for kind="tournament"."""
    from repro.core.watermark.base import FusedTail
    if tail is None:
        tail = FusedTail(kind="race", stat_dim=1)
    B, K1, V = p.shape
    K = K1 - 1
    assert q.shape == (B, K, V), (p.shape, q.shape)
    assert K < _LANES and tail.stat_dim <= _LANES, (K, tail.stat_dim)
    wm_stream, plain_resid, plain_bonus, draw_stream = (
        int(s) for s in streams)
    if live is None:
        live = jnp.ones((B,), jnp.int32)
    vp = -(-V // _LANES) * _LANES
    if vp != V:
        p = jnp.zeros((B, K1, vp), p.dtype).at[:, :, :V].set(p)
        q = jnp.zeros((B, K, vp), q.dtype).at[:, :, :V].set(q)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    row = pl.BlockSpec((1, 1, _LANES), lambda i: (i, 0, 0))
    outs = pl.pallas_call(
        functools.partial(_wm_kernel, K=K, vocab=V, kind=tail.kind,
                          m=tail.m, degenerate=tail.degenerate,
                          wm_stream=wm_stream, plain_resid=plain_resid,
                          plain_bonus=plain_bonus, draw_stream=draw_stream),
        grid=(B,),
        in_specs=[smem] * 6 + [
            pl.BlockSpec((1, K1, vp), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, K, vp), lambda i: (i, 0, 0)),
        ],
        out_specs=[row] * 4,
        out_shape=[jax.ShapeDtypeStruct((B, 1, _LANES), jnp.int32)] * 3
        + [jax.ShapeDtypeStruct((B, 1, _LANES), jnp.float32)],
        name="spec_verify_wm",
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_wm_vmem_bytes(K, vp, p.dtype, q.dtype)),
        interpret=interpret,
    )(draft_tokens.astype(jnp.int32), u.astype(jnp.float32),
      keys.astype(jnp.uint32), ctx_hashes.astype(jnp.uint32),
      seen.astype(jnp.int32), live.astype(jnp.int32), p, q)
    n_acc, acc, etok, estat = (o[:, 0] for o in outs)
    if tail.kind == "race":
        return n_acc[:, 0], acc[:, :K], etok[:, 0], estat[:, 0]
    return n_acc[:, 0], acc[:, :K], etok[:, 0], estat[:, :tail.stat_dim]
