"""Attention-based model families: dense LMs (llama/nemotron/yi/deepseek),
MoE LMs (olmoe, kimi-k2), VLM decoders with interleaved cross-attention
(llama-3.2-vision), and enc-dec audio backbones (whisper).

All stacks are ``lax.scan`` over stacked layer params (compile-time is
O(1) in depth); KV caches are stacked over layers and threaded through the
scan.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models.moe import init_moe, apply_moe
from repro.sharding.rules import constrain_batch

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Block init
# ---------------------------------------------------------------------------


def _init_block(key, cfg: ModelConfig, dtype, *, cross: bool = False,
                use_moe: bool = False) -> Params:
    k_attn, k_ffn, k_n = jax.random.split(key, 3)
    hd = cfg.resolved_head_dim
    p: Params = {
        "ln_attn": jnp.ones((cfg.d_model,), dtype),
        "ln_ffn": jnp.ones((cfg.d_model,), dtype),
        "attn": L.init_attention(k_attn, cfg.d_model, cfg.n_heads,
                                 cfg.n_kv_heads, hd, dtype),
    }
    if use_moe:
        p["moe"] = init_moe(k_ffn, cfg.d_model, cfg.moe, cfg.act, dtype)
    else:
        p["ffn"] = L.init_ffn(k_ffn, cfg.d_model, cfg.d_ff, cfg.act, dtype)
    if cross:
        p["ln_cross"] = jnp.ones((cfg.d_model,), dtype)
        p["cross"] = L.init_attention(
            jax.random.fold_in(k_attn, 7), cfg.d_model, cfg.n_heads,
            cfg.n_kv_heads, hd, dtype)
    return p


def _stack_init(key, n, init_fn):
    ps = [init_fn(k) for k in jax.random.split(key, n)]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *ps)


# ---------------------------------------------------------------------------
# Block apply — full-sequence mode
# ---------------------------------------------------------------------------


def _self_attn_seq(p, cfg, x, positions, *, causal=True):
    h = L.rms_norm(x, p["ln_attn"], cfg.rms_eps)
    q, k, v = L.qkv_proj(p["attn"], h, positions, cfg.rope_theta,
                         rope=causal)  # encoder (non-causal) skips rope? no:
    out = L.attention(q, k, v, causal=causal, window=cfg.window)
    return x + L.out_proj(p["attn"], out), (k, v)


def _cross_attn_seq(p, cfg, x, mem_kv):
    h = L.rms_norm(x, p["ln_cross"], cfg.rms_eps)
    q = jnp.einsum("bsd,dhk->bshk", h, p["cross"]["wq"])
    mk, mv = mem_kv
    out = L.attention_full(q, mk, mv, causal=False)
    return x + jnp.einsum("bshk,hkd->bsd", out, p["cross"]["wo"])


def _ffn_block(p, cfg, x, *, dropless: bool = False):
    h = L.rms_norm(x, p["ln_ffn"], cfg.rms_eps)
    if "moe" in p:
        y, aux = apply_moe(p["moe"], cfg.moe, h, cfg.act, dropless=dropless,
                           shard=cfg.moe_shard_constraints)
        return x + y, aux["lb_loss"]
    return x + L.apply_ffn(p["ffn"], h, cfg.act), jnp.float32(0.0)


def _cross_kv(p, cfg, memory):
    """Precompute cross-attention K/V from encoder memory / image emb."""
    mk = jnp.einsum("bsd,dhk->bshk", memory, p["cross"]["wk"])
    mv = jnp.einsum("bsd,dhk->bshk", memory, p["cross"]["wv"])
    return mk, mv


# ---------------------------------------------------------------------------
# Model init
# ---------------------------------------------------------------------------


def init_params(key, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    ke, kl, kh, kx, kenc = jax.random.split(key, 5)
    use_moe = cfg.moe is not None
    p: Params = {
        "embed": L.dense_init(ke, (cfg.vocab, cfg.d_model), dtype, scale=0.02),
        "ln_out": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        p["head"] = L.dense_init(kh, (cfg.d_model, cfg.vocab), dtype)

    if cfg.arch_type == "vlm":
        g = cfg.cross_attn_every
        n_groups = cfg.n_layers // g
        p["blocks"] = _stack_init(
            kl, n_groups * g,
            lambda k: _init_block(k, cfg, dtype, use_moe=use_moe))
        # reshape leading dim to (n_groups, g)
        p["blocks"] = jax.tree.map(
            lambda x: x.reshape((n_groups, g) + x.shape[1:]), p["blocks"])
        p["cross_blocks"] = _stack_init(
            kx, n_groups, lambda k: _init_block(k, cfg, dtype, cross=True))
        p["img_proj"] = L.dense_init(kx, (cfg.d_model, cfg.d_model), dtype)
    elif cfg.arch_type == "audio":
        p["enc_blocks"] = _stack_init(
            kenc, cfg.n_encoder_layers,
            lambda k: _init_block(k, cfg, dtype))
        p["audio_proj"] = L.dense_init(kenc, (cfg.d_model, cfg.d_model), dtype)
        p["blocks"] = _stack_init(
            kl, cfg.n_layers,
            lambda k: _init_block(k, cfg, dtype, cross=True))
    else:
        p["blocks"] = _stack_init(
            kl, cfg.n_layers,
            lambda k: _init_block(k, cfg, dtype, use_moe=use_moe))
    return p


def _logits(p, cfg, x):
    x = L.rms_norm(x, p["ln_out"], cfg.rms_eps)
    head = p["embed"].T if cfg.tie_embeddings else p["head"]
    return jnp.einsum("bsd,dv->bsv", x, head)


# ---------------------------------------------------------------------------
# Full-sequence forward (training / prefill compute)
# ---------------------------------------------------------------------------


def forward(params: Params, cfg: ModelConfig, batch: Dict[str, Any], *,
            remat: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (logits (B,S,V), aux_loss scalar)."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    # keep activations batch-sharded over the dp axes (pod+data) — without
    # this anchor the SPMD partitioner collapses onto the weights' FSDP axes
    # and replicates the batch across pods.
    x = constrain_batch(params["embed"][tokens])
    positions = jnp.arange(S)

    if cfg.arch_type == "audio":
        mem = _encode_audio(params, cfg, batch["audio_emb"])

        def dec_body(carry, p):
            h, aux = carry
            h, _ = _self_attn_seq(p, cfg, h, positions)
            h = _cross_attn_seq(p, cfg, h, _cross_kv(p, cfg, mem))
            h, lb = _ffn_block(p, cfg, h)
            return (h, aux + lb), None

        body = jax.checkpoint(dec_body) if remat else dec_body
        (x, aux), _ = lax.scan(body, (x, jnp.float32(0.0)), params["blocks"])
        return _logits(params, cfg, x), aux

    if cfg.arch_type == "vlm":
        img = jnp.einsum("bsd,de->bse", batch["image_emb"],
                         params["img_proj"])

        def grp_body(carry, ps):
            h, aux = carry
            blocks, xp = ps

            def self_body(c, p):
                hh, a = c
                hh, _ = _self_attn_seq(p, cfg, hh, positions)
                hh, lb = _ffn_block(p, cfg, hh)
                return (hh, a + lb), None

            (h, aux), _ = lax.scan(self_body, (h, aux), blocks)
            h = _cross_attn_seq(xp, cfg, h, _cross_kv(xp, cfg, img))
            h, lb = _ffn_block(xp, cfg, h)
            return (h, aux + lb), None

        body = jax.checkpoint(grp_body) if remat else grp_body
        (x, aux), _ = lax.scan(body, (x, jnp.float32(0.0)),
                               (params["blocks"], params["cross_blocks"]))
        return _logits(params, cfg, x), aux

    # dense / moe
    def body(carry, p):
        h, aux = carry
        h, _ = _self_attn_seq(p, cfg, h, positions)
        h, lb = _ffn_block(p, cfg, h)
        return (h, aux + lb), None

    body = jax.checkpoint(body) if remat else body
    (x, aux), _ = lax.scan(body, (x, jnp.float32(0.0)), params["blocks"])
    return _logits(params, cfg, x), aux


def _encode_audio(params, cfg, audio_emb):
    """Stub frontend carve-out: audio_emb is (B, frames, d) precomputed."""
    x = jnp.einsum("bsd,de->bse", audio_emb, params["audio_proj"])
    pos = jnp.arange(x.shape[1])

    def body(h, p):
        h, _ = _self_attn_seq(p, cfg, h, pos, causal=False)
        h, _ = _ffn_block(p, cfg, h)
        return h, None

    x, _ = lax.scan(body, x, params["enc_blocks"])
    return x


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_seq: int,
               dtype=jnp.float32) -> Dict[str, Any]:
    hd = cfg.resolved_head_dim
    kv = (batch, max_seq, cfg.n_kv_heads, hd)
    if cfg.arch_type == "vlm":
        g = cfg.cross_attn_every
        n_groups = cfg.n_layers // g
        return {
            "k": jnp.zeros((n_groups, g) + kv, dtype),
            "v": jnp.zeros((n_groups, g) + kv, dtype),
            "xk": jnp.zeros((n_groups, batch, cfg.n_image_tokens,
                             cfg.n_kv_heads, hd), dtype),
            "xv": jnp.zeros((n_groups, batch, cfg.n_image_tokens,
                             cfg.n_kv_heads, hd), dtype),
            "pos": jnp.zeros((), jnp.int32),
        }
    if cfg.arch_type == "audio":
        return {
            "k": jnp.zeros((cfg.n_layers,) + kv, dtype),
            "v": jnp.zeros((cfg.n_layers,) + kv, dtype),
            "ck": jnp.zeros((cfg.n_layers, batch, cfg.n_audio_frames,
                             cfg.n_kv_heads, hd), dtype),
            "cv": jnp.zeros((cfg.n_layers, batch, cfg.n_audio_frames,
                             cfg.n_kv_heads, hd), dtype),
            "pos": jnp.zeros((), jnp.int32),
        }
    return {
        "k": jnp.zeros((cfg.n_layers,) + kv, dtype),
        "v": jnp.zeros((cfg.n_layers,) + kv, dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


def init_paged_cache(cfg: ModelConfig, batch: int, num_pages: int,
                     page_size: int, max_pages: int,
                     dtype=jnp.float32) -> Dict[str, Any]:
    """Block-paged KV cache: a fixed pool of ``num_pages`` pages of
    ``page_size`` token slots per layer, plus a per-slot page table of
    physical page ids (0 = the reserved null page — allocators must never
    hand it out; all-zero table rows make a slot write-harmless).  Slots
    share the pool, so live concurrency is bounded by *tokens in flight*,
    not ``batch × max_seq`` rectangles.  ``extend_step`` detects the
    ``page_table`` key and reads/appends through the indirection."""
    if cfg.arch_type in ("vlm", "audio"):
        raise ValueError(
            f"paged KV caching does not support arch_type="
            f"{cfg.arch_type!r}: the cross-attention memories "
            "(image/audio frames) are per-request dense blocks, not "
            "token pages")
    hd = cfg.resolved_head_dim
    pool = (cfg.n_layers, num_pages, cfg.n_kv_heads, page_size, hd)
    return {
        "k": jnp.zeros(pool, dtype),
        "v": jnp.zeros(pool, dtype),
        "page_table": jnp.zeros((batch, max_pages), jnp.int32),
        "pos": jnp.zeros((batch,), jnp.int32),
    }


# ---------------------------------------------------------------------------
# Prefill: full-seq forward that also fills the cache.
# ---------------------------------------------------------------------------


def prefill(params: Params, cfg: ModelConfig, batch: Dict[str, Any],
            max_seq: int, cache_dtype=None) -> Tuple[jnp.ndarray, Dict]:
    tokens = batch["tokens"]
    B, S = tokens.shape
    cache_dtype = cache_dtype or params["embed"].dtype
    cache = init_cache(cfg, B, max_seq, cache_dtype)
    x = params["embed"][tokens]
    positions = jnp.arange(S)

    def write(c_arr, kv):
        return lax.dynamic_update_slice_in_dim(
            c_arr, kv.astype(c_arr.dtype), 0, axis=1)

    if cfg.arch_type == "audio":
        mem = _encode_audio(params, cfg, batch["audio_emb"])

        def body(h, p):
            h, (k, v) = _self_attn_seq(p, cfg, h, positions)
            ck, cv = _cross_kv(p, cfg, mem)
            h = _cross_attn_seq(p, cfg, h, (ck, cv))
            h, _ = _ffn_block(p, cfg, h)
            return h, (k, v, ck, cv)

        x, (ks, vs, cks, cvs) = lax.scan(body, x, params["blocks"])
        cache["k"] = jax.vmap(write)(cache["k"], ks)
        cache["v"] = jax.vmap(write)(cache["v"], vs)
        cache["ck"] = cks.astype(cache_dtype)
        cache["cv"] = cvs.astype(cache_dtype)
    elif cfg.arch_type == "vlm":
        img = jnp.einsum("bsd,de->bse", batch["image_emb"],
                         params["img_proj"])

        def grp_body(h, ps):
            blocks, xp = ps

            def self_body(hh, p):
                hh, (k, v) = _self_attn_seq(p, cfg, hh, positions)
                hh, _ = _ffn_block(p, cfg, hh)
                return hh, (k, v)

            h, (ks, vs) = lax.scan(self_body, h, blocks)
            xk, xv = _cross_kv(xp, cfg, img)
            h = _cross_attn_seq(xp, cfg, h, (xk, xv))
            h, _ = _ffn_block(xp, cfg, h)
            return h, (ks, vs, xk, xv)

        x, (ks, vs, xks, xvs) = lax.scan(grp_body, x,
                                         (params["blocks"],
                                          params["cross_blocks"]))
        cache["k"] = jax.vmap(jax.vmap(write))(cache["k"], ks)
        cache["v"] = jax.vmap(jax.vmap(write))(cache["v"], vs)
        cache["xk"] = xks.astype(cache_dtype)
        cache["xv"] = xvs.astype(cache_dtype)
    else:
        def body(h, p):
            h, (k, v) = _self_attn_seq(p, cfg, h, positions)
            h, _ = _ffn_block(p, cfg, h)
            return h, (k, v)

        x, (ks, vs) = lax.scan(body, x, params["blocks"])
        cache["k"] = jax.vmap(write)(cache["k"], ks)
        cache["v"] = jax.vmap(write)(cache["v"], vs)

    cache["pos"] = jnp.asarray(S, jnp.int32)
    return _logits(params, cfg, x), cache


# ---------------------------------------------------------------------------
# Decode step: one token per sequence against the cache.
# ---------------------------------------------------------------------------


def _self_attn_step(p, cfg, x, cache_k, cache_v, pos):
    """x: (B,Sq,d); caches: (B,S,Hkv,hd); pos: () or (B,)."""
    B, Sq = x.shape[:2]
    h = L.rms_norm(x, p["ln_attn"], cfg.rms_eps)
    pos_b = jnp.atleast_1d(pos)
    positions = pos_b[:, None] + jnp.arange(Sq)[None]        # (B|1, Sq)
    q, k, v = L.qkv_proj(p["attn"], h, positions, cfg.rope_theta)
    cache_k = L.cache_write(cache_k, k, pos)
    cache_v = L.cache_write(cache_v, v, pos)
    out = L.decode_attention(q, cache_k, cache_v, pos + 1, window=cfg.window,
                             grouped=cfg.opt_decode)
    return x + L.out_proj(p["attn"], out), cache_k, cache_v


def _self_attn_step_paged(p, cfg, x, pool_k, pool_v, table, pos):
    """Paged twin of ``_self_attn_step``: x (B,Sq,d); pools (P, Hkv,
    page_size, hd); table (B, max_pages) physical page ids; pos (B,).  Reads and
    appends go through the page indirection; the math (RoPE positions,
    position-gated masked softmax) is identical, so the output is
    bit-identical to the dense path over the same logical entries."""
    B, Sq = x.shape[:2]
    h = L.rms_norm(x, p["ln_attn"], cfg.rms_eps)
    pos_b = jnp.atleast_1d(pos)
    positions = pos_b[:, None] + jnp.arange(Sq)[None]        # (B|1, Sq)
    q, k, v = L.qkv_proj(p["attn"], h, positions, cfg.rope_theta)
    pool_k = L.paged_cache_write(pool_k, table, k, pos)
    pool_v = L.paged_cache_write(pool_v, table, v, pos)
    out = L.paged_decode_attention(q, pool_k, pool_v, table, pos + 1,
                                   window=cfg.window, grouped=cfg.opt_decode)
    return x + L.out_proj(p["attn"], out), pool_k, pool_v


def _cross_attn_step(p, cfg, x, xk, xv):
    h = L.rms_norm(x, p["ln_cross"], cfg.rms_eps)
    q = jnp.einsum("bsd,dhk->bshk", h, p["cross"]["wq"])
    out = L.attention_full(q, xk, xv, causal=False)
    return x + jnp.einsum("bshk,hkd->bsd", out, p["cross"]["wo"])


def decode_step(params: Params, cfg: ModelConfig, token: jnp.ndarray,
                cache: Dict[str, Any]) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """token: (B,) int32 -> (logits (B,V), new cache)."""
    logits, cache = extend_step(params, cfg, token[:, None], cache)
    return logits[:, 0], cache


def extend_step(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                cache: Dict[str, Any]) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """Speculative verification step: run Sq>=1 tokens through the model
    continuing from the cache.  tokens: (B,Sq) -> (logits (B,Sq,V), cache).

    ``cache["pos"]`` may be a scalar or per-sequence (B,) (divergent
    speculative acceptance)."""
    pos = cache["pos"]
    Sq = tokens.shape[1]
    x = params["embed"][tokens]              # (B,Sq,d)

    if cfg.arch_type == "vlm":
        def grp_body(h, ps):
            blocks, xp, ck, cv, xk, xv = ps

            def self_body(hh, inner):
                p, k_l, v_l = inner
                hh, k_l, v_l = _self_attn_step(p, cfg, hh, k_l, v_l, pos)
                hh, _ = _ffn_block(p, cfg, hh, dropless=True)
                return hh, (k_l, v_l)

            h, (ck, cv) = lax.scan(self_body, h, (blocks, ck, cv))
            h = _cross_attn_step(xp, cfg, h, xk, xv)
            h, _ = _ffn_block(xp, cfg, h, dropless=True)
            return h, (ck, cv)

        x, (ck, cv) = lax.scan(
            grp_body, x,
            (params["blocks"], params["cross_blocks"], cache["k"], cache["v"],
             cache["xk"], cache["xv"]))
        cache = dict(cache, k=ck, v=cv, pos=pos + Sq)
    elif cfg.arch_type == "audio":
        def body(h, inner):
            p, k_l, v_l, ck_l, cv_l = inner
            h, k_l, v_l = _self_attn_step(p, cfg, h, k_l, v_l, pos)
            h = _cross_attn_step(p, cfg, h, ck_l, cv_l)
            h, _ = _ffn_block(p, cfg, h, dropless=True)
            return h, (k_l, v_l)

        x, (ck, cv) = lax.scan(
            body, x, (params["blocks"], cache["k"], cache["v"],
                      cache["ck"], cache["cv"]))
        cache = dict(cache, k=ck, v=cv, pos=pos + Sq)
    elif "page_table" in cache:
        table = cache["page_table"]

        def body(h, inner):
            p, k_l, v_l = inner
            h, k_l, v_l = _self_attn_step_paged(p, cfg, h, k_l, v_l,
                                                table, pos)
            h, _ = _ffn_block(p, cfg, h, dropless=True)
            return h, (k_l, v_l)

        x, (ck, cv) = lax.scan(body, x,
                               (params["blocks"], cache["k"], cache["v"]))
        cache = dict(cache, k=ck, v=cv, pos=pos + Sq)
    else:
        def body(h, inner):
            p, k_l, v_l = inner
            h, k_l, v_l = _self_attn_step(p, cfg, h, k_l, v_l, pos)
            h, _ = _ffn_block(p, cfg, h, dropless=True)
            return h, (k_l, v_l)

        x, (ck, cv) = lax.scan(body, x,
                               (params["blocks"], cache["k"], cache["v"]))
        cache = dict(cache, k=ck, v=cv, pos=pos + Sq)

    return _logits(params, cfg, x), cache
