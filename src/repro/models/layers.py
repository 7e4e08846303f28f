"""Shared neural-net layers: RMSNorm, RoPE, GQA attention (full, blockwise,
sliding-window, decode-with-cache), FFN activations, embeddings.

Everything is functional: ``init_*`` builds a params dict, ``apply`` fns are
pure.  All matmuls use explicit einsums so sharding propagation is clean.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

# ---------------------------------------------------------------------------
# Basic ops
# ---------------------------------------------------------------------------


def rms_norm(x, scale, eps=1e-5):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    out = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32)).astype(x.dtype)


def activation(name: str):
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":
        return jax.nn.gelu
    if name == "sqrelu":
        return lambda x: jnp.square(jax.nn.relu(x))
    raise ValueError(f"unknown activation {name}")


def dense_init(key, shape, dtype, scale: Optional[float] = None):
    fan_in = shape[0] if len(shape) >= 2 else 1
    std = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape) * std).astype(dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float):
    return theta ** (-jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, hd), positions: (..., S) int32."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    angles = angles[..., None, :]                       # (..., S, 1, hd/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention params
# ---------------------------------------------------------------------------


def init_attention(key, d_model, n_heads, n_kv_heads, head_dim, dtype):
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": dense_init(kq, (d_model, n_heads, head_dim), dtype),
        "wk": dense_init(kk, (d_model, n_kv_heads, head_dim), dtype),
        "wv": dense_init(kv, (d_model, n_kv_heads, head_dim), dtype),
        "wo": dense_init(ko, (n_heads, head_dim, d_model), dtype),
    }


def qkv_proj(params, x, positions, theta, rope: bool = True):
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if rope:
        q = apply_rope(q, positions, theta)
        k = apply_rope(k, positions, theta)
    return q, k, v


def out_proj(params, attn_out):
    return jnp.einsum("bshk,hkd->bsd", attn_out, params["wo"])


def _repeat_kv(k, n_rep: int):
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(
        b, s, h * n_rep, d)


# ---------------------------------------------------------------------------
# Full (naive) attention — reference path and small-seq path.
# ---------------------------------------------------------------------------


def attention_full(q, k, v, *, causal=True, window=0, q_positions=None,
                   kv_positions=None, mask=None):
    """q: (B,Sq,H,hd); k,v: (B,Sk,Hkv,hd). Returns (B,Sq,H,hd)."""
    n_rep = q.shape[2] // k.shape[2]
    k, v = _repeat_kv(k, n_rep), _repeat_kv(v, n_rep)
    scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bqhk,bshk->bhqs", q, k).astype(jnp.float32) * scale
    if q_positions is None:
        q_positions = jnp.arange(q.shape[1])
    if kv_positions is None:
        kv_positions = jnp.arange(k.shape[1])
    big_neg = jnp.finfo(jnp.float32).min
    if causal:
        cmask = q_positions[:, None] >= kv_positions[None, :]
        if window:
            cmask &= q_positions[:, None] - kv_positions[None, :] < window
        scores = jnp.where(cmask[None, None], scores, big_neg)
    if mask is not None:  # (B, Sq, Sk) or (Sq, Sk) extra mask
        m = mask if mask.ndim == 3 else mask[None]
        scores = jnp.where(m[:, None], scores, big_neg)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqs,bshk->bqhk", probs.astype(v.dtype), v)
    return out


# ---------------------------------------------------------------------------
# Blockwise (flash-style) attention in pure JAX — memory-bounded for long
# sequences.  Online softmax over kv blocks; scan over q blocks.
# Baseline iterates ALL kv blocks per q block and masks (see EXPERIMENTS.md
# §Perf for the causal-skip optimized variant).
# ---------------------------------------------------------------------------


def attention_blockwise(q, k, v, *, causal=True, window=0,
                        q_block=512, kv_block=512, skip_masked_blocks=True):
    """Flash-attention structure in pure JAX.

    When ``skip_masked_blocks`` is set (the optimized path), each q block only
    scans kv blocks that intersect its causal/window band, bounding both
    memory AND flops; otherwise all kv blocks are visited and masked.
    """
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    n_rep = H // k.shape[2]
    k, v = _repeat_kv(k, n_rep), _repeat_kv(v, n_rep)
    q_block = min(q_block, Sq)
    kv_block = min(kv_block, Sk)
    # pad to multiples
    def pad_to(x, axis, mult):
        pad = (-x.shape[axis]) % mult
        if pad == 0:
            return x, 0
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad)
        return jnp.pad(x, widths), pad

    q, _qpad = pad_to(q, 1, q_block)
    k, _kpad = pad_to(k, 1, kv_block)
    v, _ = pad_to(v, 1, kv_block)
    nq, nk = q.shape[1] // q_block, k.shape[1] // kv_block
    scale = 1.0 / math.sqrt(hd)
    kb = k.reshape(B, nk, kv_block, H, hd)
    vb = v.reshape(B, nk, kv_block, H, hd)
    qb = q.reshape(B, nq, q_block, H, hd)
    big_neg = jnp.float32(-1e30)

    def one_q_block(qi, qblk):
        q_pos = qi * q_block + jnp.arange(q_block)

        def kv_step(carry, kj):
            m_prev, l_prev, acc = carry
            kblk = lax.dynamic_index_in_dim(kb, kj, axis=1, keepdims=False)
            vblk = lax.dynamic_index_in_dim(vb, kj, axis=1, keepdims=False)
            s = jnp.einsum("bqhk,bshk->bhqs", qblk, kblk).astype(
                jnp.float32) * scale
            kv_pos = kj * kv_block + jnp.arange(kv_block)
            valid = kv_pos[None, :] < Sk
            if causal:
                valid &= q_pos[:, None] >= kv_pos[None, :]
                if window:
                    valid &= q_pos[:, None] - kv_pos[None, :] < window
            s = jnp.where(valid[None, None], s, big_neg)
            m_new = jnp.maximum(m_prev, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + p.sum(axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhqs,bshk->bhqk", p, vblk.astype(jnp.float32))
            return (m_new, l_new, acc), None

        m0 = jnp.full((B, H, q_block), big_neg)
        l0 = jnp.zeros((B, H, q_block))
        acc0 = jnp.zeros((B, H, q_block, hd))
        if skip_masked_blocks and causal and not window:
            # only kv blocks 0..ceil((qi+1)*q_block / kv_block)-1 intersect
            n_needed = (qi * q_block + q_block + kv_block - 1) // kv_block
            n_needed = min(n_needed, nk)
            (m, l, acc), _ = lax.scan(kv_step, (m0, l0, acc0),
                                      jnp.arange(n_needed))
        elif skip_masked_blocks and causal and window:
            lo = max(0, (qi * q_block - window) // kv_block)
            hi = min(nk, (qi * q_block + q_block + kv_block - 1) // kv_block)
            (m, l, acc), _ = lax.scan(kv_step, (m0, l0, acc0),
                                      jnp.arange(lo, hi))
        else:
            (m, l, acc), _ = lax.scan(kv_step, (m0, l0, acc0), jnp.arange(nk))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out.astype(q.dtype).transpose(0, 2, 1, 3)  # (B, qblk, H, hd)

    outs = [one_q_block(i, qb[:, i]) for i in range(nq)]
    out = jnp.concatenate(outs, axis=1)[:, :Sq]
    return out


def attention(q, k, v, *, causal=True, window=0, blockwise_threshold=2048,
              q_block=512, kv_block=512, skip_masked_blocks=True):
    """Dispatch: naive for short sequences, blockwise beyond the threshold."""
    if q.shape[1] * k.shape[1] <= blockwise_threshold ** 2:
        return attention_full(q, k, v, causal=causal, window=window)
    return attention_blockwise(q, k, v, causal=causal, window=window,
                               q_block=q_block, kv_block=kv_block,
                               skip_masked_blocks=skip_masked_blocks)


# ---------------------------------------------------------------------------
# Decode attention against a KV cache.
# ---------------------------------------------------------------------------


def decode_attention(q, k_cache, v_cache, pos, *, window=0, grouped=False):
    """q: (B,Sq,H,hd); caches: (B,S,Hkv,hd); pos: () or (B,) sequence length
    AFTER the first query token (i.e. query i attends to cache[< pos+i]).

    Attends to cache positions [0, pos) (or the trailing ``window``).

    ``grouped=True`` (opt_decode): GQA queries are folded to
    (B,Sq,Hkv,n_rep,hd) and contracted directly against the cache — no
    n_rep-times materialized KV broadcast — and the scores are constrained
    to stay sequence-sharded through the softmax (partial max/sum
    all-reduce instead of an all-gather of the cache)."""
    B, S, Hkv, hd = k_cache.shape
    Sq = q.shape[1]
    n_rep = q.shape[2] // Hkv
    scale = 1.0 / math.sqrt(hd)
    if grouped and not (window and window < S):
        from repro.sharding.rules import constrain_dims
        pos_b = jnp.broadcast_to(jnp.atleast_1d(pos), (B,))
        q_off = jnp.arange(Sq)
        kv_pos = jnp.arange(S)[None]
        valid = kv_pos[:, None, :] < (pos_b[:, None] + q_off)[:, :, None]
        qg = q.reshape(B, Sq, Hkv, n_rep, hd)
        scores = jnp.einsum("bqgrk,bsgk->bgrqs", qg,
                            k_cache).astype(jnp.float32) * scale
        scores = jnp.where(valid[:, None, None], scores,
                           jnp.finfo(jnp.float32).min)
        scores = constrain_dims(scores, ("dp", None, None, None, "model"))
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bgrqs,bsgk->bqgrk", probs.astype(v_cache.dtype),
                         v_cache)
        return out.reshape(B, Sq, Hkv * n_rep, hd)
    pos_b = jnp.broadcast_to(jnp.atleast_1d(pos), (B,))       # (B,)
    q_off = jnp.arange(Sq)                                    # (Sq,)
    if window and window < S:
        # gather the trailing window with a per-sequence dynamic slice
        start = jnp.maximum(pos_b + Sq - 1 - window, 0)       # (B,)
        k_cache = jax.vmap(
            lambda c, s: lax.dynamic_slice_in_dim(c, s, window, axis=0)
        )(k_cache, start)
        v_cache = jax.vmap(
            lambda c, s: lax.dynamic_slice_in_dim(c, s, window, axis=0)
        )(v_cache, start)
        kv_pos = start[:, None] + jnp.arange(window)[None]    # (B, W)
        valid = (kv_pos[:, None, :] < (pos_b[:, None] + q_off)[:, :, None])
        valid &= ((pos_b[:, None] + q_off)[:, :, None] - kv_pos[:, None, :]
                  <= window)
    else:
        kv_pos = jnp.arange(S)[None]                          # (1, S)
        valid = kv_pos[:, None, :] < (pos_b[:, None] + q_off)[:, :, None]
    k = _repeat_kv(k_cache, n_rep)
    v = _repeat_kv(v_cache, n_rep)
    scores = jnp.einsum("bqhk,bshk->bhqs", q, k).astype(jnp.float32) * scale
    scores = jnp.where(valid[:, None], scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqs,bshk->bqhk", probs.astype(v.dtype), v)


def paged_decode_attention(q, k_pool, v_pool, page_table, pos, *, window=0,
                           grouped=False):
    """Decode attention through page indirection: pools (P, Hkv,
    page_size, hd) + per-slot page tables (B, max_pages) of physical page
    ids (0 = the reserved null page) replace the dense (B, S, Hkv, hd)
    cache.

    Dispatch lives in ``kernels.paged_attention``: on CPU the jnp mirror
    (gather + ``decode_attention``), bit-identical to ``decode_attention``
    over a dense cache holding the same entries; on TPU the Pallas kernel
    (page-table-driven block gathers, page-wise online softmax), equal to
    it up to rounding."""
    from repro.kernels.paged_attention import paged_decode_attention as _pa
    return _pa(q, k_pool, v_pool, page_table, pos, window=window,
               grouped=grouped)


def paged_cache_write(pool, page_table, kv, pos):
    """Write kv (B, Sq, Hkv, hd) into a paged pool (P, Hkv, page_size, hd)
    at logical positions pos..pos+Sq-1 of each slot, routed through the
    slot's page-table row (B, max_pages).  Logical positions beyond the
    table (or on null-page tails) land in page 0, whose contents are
    position-gated out of every read."""
    page_size = pool.shape[2]
    B, Sq = kv.shape[:2]
    max_pages = page_table.shape[1]
    pos_b = jnp.broadcast_to(jnp.atleast_1d(pos), (B,))
    idx = pos_b[:, None] + jnp.arange(Sq)[None]              # (B, Sq) logical
    lpage = idx // page_size
    phys = jnp.take_along_axis(page_table,
                               jnp.minimum(lpage, max_pages - 1), axis=1)
    phys = jnp.where(lpage < max_pages, phys, 0)             # (B, Sq)
    # advanced indices around a slice: the indexed view is (B, Sq, Hkv, hd)
    return pool.at[phys, :, idx % page_size].set(kv.astype(pool.dtype))


def cache_write(cache, kv, pos):
    """Write kv (B,Sq,Hkv,hd) into cache (B,S,Hkv,hd) at positions
    pos..pos+Sq-1 (pos scalar) or per-sequence pos (B,)."""
    if jnp.ndim(pos) == 0:
        return lax.dynamic_update_slice_in_dim(
            cache, kv.astype(cache.dtype), pos, axis=1)
    B, Sq = kv.shape[:2]
    idx = pos[:, None] + jnp.arange(Sq)[None]                # (B, Sq)
    return cache.at[jnp.arange(B)[:, None], idx].set(kv.astype(cache.dtype))


# ---------------------------------------------------------------------------
# FFN (SwiGLU-style 3-matrix, or 2-matrix for gelu/sqrelu archs)
# ---------------------------------------------------------------------------


def init_ffn(key, d_model, d_ff, act, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "w_in": dense_init(k1, (d_model, d_ff), dtype),
        "w_out": dense_init(k2, (d_ff, d_model), dtype),
    }
    if act == "silu":  # gated
        p["w_gate"] = dense_init(k3, (d_model, d_ff), dtype)
    return p


def apply_ffn(params, x, act: str):
    f = activation(act)
    h = jnp.einsum("...d,df->...f", x, params["w_in"])
    if "w_gate" in params:
        h = f(jnp.einsum("...d,df->...f", x, params["w_gate"])) * h
    else:
        h = f(h)
    return jnp.einsum("...f,fd->...d", h, params["w_out"])
