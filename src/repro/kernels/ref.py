"""Pure-jnp oracles for the Pallas kernels (bit-exact PRF mirror)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import prf


def gumbel_argmax_ref(probs, seeds):
    """probs (B,V), seeds (B,) -> (tokens (B,), u (B,))."""
    B, V = probs.shape
    w = jnp.arange(V, dtype=jnp.uint32)

    def one(p, s):
        u = prf.kernel_uniform(s, w)
        score = jnp.log(u) / jnp.maximum(p.astype(jnp.float32), 1e-30)
        score = jnp.where(p > 0, score, -jnp.inf)
        tok = jnp.argmax(score).astype(jnp.int32)
        return tok, u[tok]

    return jax.vmap(one)(probs, seeds.astype(jnp.uint32))


def tournament_ref(probs, seeds, *, m: int = 30):
    """probs (B,V), seeds (B,) -> m-round tournament distribution (B,V).

    Runs at the 128-lane padded extent (zero pad lanes), matching the
    kernel's reduction extent — XLA float reductions are not bit-invariant
    to the reduced extent, so the mirror must pad exactly like the kernel
    does.  Unlike ``synthid.tournament_padded`` this applies the operator
    to the row as-is (no normalization), mirroring ``tournament_kernel``."""
    B, V = probs.shape
    vp = -(-V // 128) * 128
    w = jnp.arange(vp, dtype=jnp.uint32)

    def one(p, s):
        p = jnp.zeros((vp,), jnp.float32).at[:V].set(p.astype(jnp.float32))

        def body(i, p):
            g = prf.kernel_gbit(s, w + jnp.uint32(V) * jnp.uint32(i))
            mass = jnp.sum(p * g)
            return p * (1.0 + g - mass)

        return jax.lax.fori_loop(0, m, body, p)[:V]

    return jax.vmap(one)(probs, seeds.astype(jnp.uint32))


def tournament_keyed_ref(probs, keys, ctx_hashes, *, stream: int,
                         m: int = 30):
    """Mirror of ``tournament_keyed_kernel``: derive each row's g-seed
    from its key word via the host seed chain, then the padded-extent
    rounds of ``tournament_ref``."""
    seeds = prf.wm_seed(keys.astype(jnp.uint32),
                        ctx_hashes.astype(jnp.uint32), stream)
    return tournament_ref(probs, seeds, m=m)


def spec_verify_ref(p, q, draft_tokens, u, resid_seeds):
    """Mirror of spec_verify_kernel; see its docstring."""
    B, K, V = p.shape
    p = p.astype(jnp.float32)
    q = q.astype(jnp.float32)
    p_tok = jnp.take_along_axis(
        p, draft_tokens[..., None].astype(jnp.int32), axis=-1)[..., 0]
    q_tok = jnp.take_along_axis(
        q, draft_tokens[..., None].astype(jnp.int32), axis=-1)[..., 0]
    a = jnp.minimum(1.0, p_tok / jnp.maximum(q_tok, 1e-30))
    ok = (u < a).astype(jnp.int32)
    prefix = jnp.cumprod(ok, axis=-1)
    n_acc = prefix.sum(axis=-1).astype(jnp.int32)
    slot = jnp.minimum(n_acc, K - 1)
    p_s = jnp.take_along_axis(p, slot[:, None, None], axis=1)[:, 0]
    q_s = jnp.take_along_axis(q, slot[:, None, None], axis=1)[:, 0]
    seed_s = jnp.take_along_axis(
        resid_seeds.astype(jnp.uint32), slot[:, None], axis=1)[:, 0]
    r = jnp.maximum(p_s - q_s, 0.0)
    w = jnp.arange(V, dtype=jnp.uint32)

    def race(r_row, s):
        uv = prf.kernel_uniform(s, w)
        score = jnp.log(uv) / jnp.maximum(r_row, 1e-30)
        score = jnp.where(r_row > 0, score, -jnp.inf)
        tok = jnp.argmax(score).astype(jnp.int32)
        return tok, uv[tok]

    rtok, ru = jax.vmap(race)(r, seed_s)
    return n_acc, prefix, rtok, ru


def spec_verify_wm_ref(p, q, draft_tokens, u, keys, ctx_hashes, seen,
                       live=None, *, streams, tail=None):
    """Mirror of spec_verify_wm_kernel (full watermarked Alg. 1 tail);
    see its docstring.  p: (B, K+1, V), q: (B, K, V); keys (B,) uint32 key
    words; ctx_hashes (B, K+1) uint32; ``streams`` the static
    ``(wm_stream, plain_resid, plain_bonus, draw_stream)`` tuple.  Per-slot
    seeds come from the same two-link chain the kernel runs in VMEM
    (``prf.wm_seed``).  ``live`` (optional, (B,)): rows with live == 0
    return the kernel's zero-initialized outputs (drained
    continuous-batching slots).  ``tail`` selects the scheme's
    emitted-token branch (default: Gumbel race); kind="tournament" runs
    the m-round SynthID tournament at the 128-lane padded extent — the
    exact reduction extent of the kernel — via the canonical
    ``synthid.tournament_padded`` math, and returns the emitted token's
    m g-bits as the 4th output."""
    from repro.core.watermark import synthid as _synthid
    from repro.core.watermark.base import FusedTail
    if tail is None:
        tail = FusedTail(kind="race", stat_dim=1)
    wm_stream, plain_resid, plain_bonus, draw_stream = streams
    B, K1, V = p.shape
    K = K1 - 1
    p = p.astype(jnp.float32)
    q = q.astype(jnp.float32)
    p_tok = jnp.take_along_axis(
        p[:, :K], draft_tokens[..., None].astype(jnp.int32), axis=-1)[..., 0]
    q_tok = jnp.take_along_axis(
        q, draft_tokens[..., None].astype(jnp.int32), axis=-1)[..., 0]
    a = jnp.minimum(1.0, p_tok / jnp.maximum(q_tok, 1e-30))
    prefix = jnp.cumprod((u < a).astype(jnp.int32), axis=-1)
    n_acc = prefix.sum(axis=-1).astype(jnp.int32)
    slot = n_acc                                        # in [0, K]
    p_s = jnp.take_along_axis(p, slot[:, None, None], axis=1)[:, 0]
    q_ext = jnp.concatenate([q, jnp.zeros((B, 1, V), q.dtype)], axis=1)
    q_s = jnp.take_along_axis(q_ext, slot[:, None, None], axis=1)[:, 0]
    seen_s = jnp.take_along_axis(seen.astype(jnp.int32), slot[:, None],
                                 axis=1)[:, 0]
    kw = keys.astype(jnp.uint32)
    ctx_s = jnp.take_along_axis(ctx_hashes.astype(jnp.uint32),
                                slot[:, None], axis=1)[:, 0]
    pl_stream = jnp.where(slot == K, jnp.uint32(plain_bonus),
                          jnp.uint32(plain_resid))
    wm_s = prf.wm_seed(kw, ctx_s, wm_stream)
    pl_s = prf.wm_seed(kw, ctx_s, pl_stream)
    r = jnp.maximum(p_s - q_s, 0.0)                     # bonus dist at slot K
    w = jnp.arange(V, dtype=jnp.uint32)

    def race(r_row, s):
        uv = prf.kernel_uniform(s, w)
        score = jnp.log(uv) / jnp.maximum(r_row, 1e-30)
        score = jnp.where(r_row > 0, score, -jnp.inf)
        tok = jnp.argmax(score).astype(jnp.int32)
        return tok, uv[tok]

    if tail.kind == "race":
        seed_s = jnp.where(seen_s != 0, pl_s, wm_s)
        etok, estat = jax.vmap(race)(r, seed_s)
    else:                           # kind == "tournament" (SynthID)
        m = tail.m
        dw_s = prf.wm_seed(kw, ctx_s, draw_stream)

        def tourney(r_row, sn, g_seed, dw, plc):
            pz = _synthid.tournament_padded(r_row, g_seed, m=m, vocab=V)
            vp = pz.shape[-1]
            rn = jnp.zeros((vp,), jnp.float32).at[:V].set(r_row)
            rn = rn / jnp.maximum(jnp.sum(rn), 1e-30)
            race_dist = jnp.where(sn != 0, rn, pz)
            race_seed = jnp.where(sn != 0, plc, dw)
            race_tok = _synthid.race_padded(race_dist, race_seed, vocab=V)
            if tail.degenerate:
                tok = jnp.where(sn != 0, race_tok,
                                _synthid.argmax_padded(pz, vocab=V))
            else:
                tok = race_tok
            return tok, _synthid.token_stat(g_seed, tok, V, m=m)

        etok, estat = jax.vmap(tourney)(r, seen_s, wm_s, dw_s, pl_s)
    if live is not None:
        lv = live.astype(bool)
        n_acc = jnp.where(lv, n_acc, 0)
        prefix = jnp.where(lv[:, None], prefix, 0)
        etok = jnp.where(lv, etok, 0)
        estat = jnp.where(lv if estat.ndim == 1 else lv[:, None], estat,
                          0.0)
    return n_acc, prefix, etok, estat


# ---------------------------------------------------------------------------
# Fused single-step recurrent decode mirrors (kernels/wkv.py, kernels/ssd.py)
# ---------------------------------------------------------------------------


def wkv_step_ref(r, k, v, w, u, s0):
    """Bit-exact mirror of ``wkv.wkv_step_kernel`` — and exactly one step
    of the ``wkv.wkv_ref`` scan (identical op sequence), so fused-step
    decode splices bit-exactly onto a chunked-scan prefill prefix.

    r,k,v,w: (B,H,hd); u: (H,hd); s0: (B,H,hd,hd) f32.
    Returns (y (B,H,hd) f32, s1 (B,H,hd,hd) f32)."""
    r, k, v, w = (a.astype(jnp.float32) for a in (r, k, v, w))
    u = u.astype(jnp.float32)
    s = s0.astype(jnp.float32)
    kv = k[..., :, None] * v[..., None, :]
    # multiply+sum, not einsum: the exact op sequence of the kernel body
    # (and the chunked kernel's in-block form) — bit-stable across batch
    # shapes and jit/eager, which a dot_general lowering is not.
    y = jnp.sum((s + u[..., :, None] * kv) * r[..., :, None], axis=-2)
    return y, w[..., :, None] * s + kv


def ssd_step_ref(la, dtx, Bf, Cf, h0):
    """Bit-exact mirror of ``ssd.ssd_step_kernel`` — one step of the
    ``ssd.ssd_ref`` scan (identical op sequence).

    la: (B,H) log-decay (<=0); dtx: (B,H,hd); Bf, Cf: (B,N);
    h0: (B,H,hd,N) f32.  Returns (y (B,H,hd) f32, h1 (B,H,hd,N) f32)."""
    la, dtx, Bf, Cf = (a.astype(jnp.float32) for a in (la, dtx, Bf, Cf))
    h = jnp.exp(la)[..., None, None] * h0.astype(jnp.float32) \
        + dtx[..., None] * Bf[:, None, None, :]
    y = jnp.sum(h * Cf[:, None, None, :], axis=-1)   # kernel's exact form
    return y, h


# ---------------------------------------------------------------------------
# Paged-decode attention mirror (kernels/paged_attention.py)
# ---------------------------------------------------------------------------


def paged_gather(pool, page_table):
    """Materialize a slot-major dense view of a paged KV pool.

    pool (P, Hkv, page_size, hd), page_table (B, max_pages) physical page
    ids -> (B, max_pages * page_size, Hkv, hd), logical position order.
    Null-page (id 0) tails gather garbage at logical positions >= the
    slot's allocation, which the position gate masks before the softmax."""
    B, n_pages = page_table.shape
    _, Hkv, page_size, hd = pool.shape
    pages = pool[page_table].transpose(0, 1, 3, 2, 4)  # (B, n, ps, Hkv, hd)
    return pages.reshape(B, n_pages * page_size, Hkv, hd)


def paged_attention_ref(q, k_pool, v_pool, page_table, pos, *, window=0,
                        grouped=False):
    """Bit-exact jnp mirror of ``paged_attention_kernel`` — and the CPU
    serving path: the page-table gather followed by the unchanged dense
    ``decode_attention`` math.  Masked lanes (including everything a null
    page gathers) use the same ``finfo.min`` sentinel as the kernel, so
    the softmax is invariant to the gathered extent and the output is
    bit-identical to dense caching (the slot-isolation contract)."""
    from repro.models import layers as L
    k = paged_gather(k_pool, page_table)
    v = paged_gather(v_pool, page_table)
    return L.decode_attention(q, k, v, pos, window=window, grouped=grouped)
