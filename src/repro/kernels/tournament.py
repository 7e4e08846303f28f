"""SynthID m-round tournament decode kernel.

Applies the tournament operator (paper Eq. 4)

    T_g(P)(w) = P_w * (1 + g_w - sum_{w': g_{w'}=1} P_{w'})

m times with per-round g-bits generated from the in-kernel integer PRF
(bit-exact with ``repro.core.prf.kernel_gbit``).  The full vocab row stays
resident in VMEM across all m rounds — the GPU implementation materializes
m (V,)-vectors in HBM; on TPU the whole composition is one HBM read of the
probs row and one write of the final distribution.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.gumbel_argmax import _hash_u32, _MIX, _seed_chain


def _gbit(seed, counter):
    bits = _hash_u32(seed * _MIX ^ _hash_u32(counter))
    return (bits >> np.uint32(31)).astype(jnp.int32).astype(jnp.float32)


def _rounds(p, seeds, w, *, m: int, vocab: int):
    """The m tournament rounds over a (bm, Vp) block; ``seeds`` (bm, 1)."""
    p = jnp.where(w < vocab, p, 0.0)

    def round_body(i, p):
        counter = w + np.uint32(vocab) * i.astype(jnp.uint32)
        g = _gbit(seeds, counter)
        mass_one = jnp.sum(p * g, axis=-1, keepdims=True)
        return p * (1.0 + g - mass_one)

    return jax.lax.fori_loop(0, m, round_body, p)


def _kernel(probs_ref, seed_ref, out_ref, *, m: int, vocab: int):
    p = probs_ref[...].astype(jnp.float32)             # (bm, Vp)
    bm, vp = p.shape
    w = jax.lax.broadcasted_iota(jnp.uint32, (bm, vp), 1)
    seeds = seed_ref[...].astype(jnp.uint32)[:, None]
    out_ref[...] = _rounds(p, seeds, w, m=m, vocab=vocab)


def _keyed_kernel(probs_ref, key_ref, ctx_ref, out_ref, *, m: int,
                  vocab: int, stream: int):
    """Same rounds, but the per-row g-seed is re-derived in VMEM from the
    row's key word and context hash (``chain(chain(key, stream), ctx)``)."""
    p = probs_ref[...].astype(jnp.float32)             # (bm, Vp)
    bm, vp = p.shape
    w = jax.lax.broadcasted_iota(jnp.uint32, (bm, vp), 1)
    keys = key_ref[...].astype(jnp.uint32)
    ctx = ctx_ref[...].astype(jnp.uint32)
    seeds = _seed_chain(_seed_chain(keys, jnp.uint32(stream)), ctx)[:, None]
    out_ref[...] = _rounds(p, seeds, w, m=m, vocab=vocab)


def tournament_kernel(probs, seeds, *, m: int = 30, block_rows: int = 4,
                      interpret: bool = False):
    """probs: (B, V) normalized; seeds: (B,) uint32.
    Returns the m-round tournament distribution (B, V) f32."""
    B, V = probs.shape
    vp = -(-V // 128) * 128
    bp = -(-B // block_rows) * block_rows
    probs_p = jnp.zeros((bp, vp), probs.dtype).at[:B, :V].set(probs)
    seeds_p = jnp.zeros((bp,), jnp.uint32).at[:B].set(
        seeds.astype(jnp.uint32))
    out = pl.pallas_call(
        functools.partial(_kernel, m=m, vocab=V),
        grid=(bp // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, vp), lambda i: (i, 0)),
            pl.BlockSpec((block_rows,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((block_rows, vp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bp, vp), jnp.float32),
        interpret=interpret,
    )(probs_p, seeds_p)
    return out[:B, :V]


def tournament_keyed_kernel(probs, keys, ctx_hashes, *, stream: int,
                            m: int = 30, block_rows: int = 4,
                            interpret: bool = False):
    """probs: (B, V) normalized; keys: (B,) uint32 key words; ctx_hashes:
    (B,) uint32.  Per-row g-seeds are derived in-kernel from the key row
    (the multi-tenant path — no host seed tensor), then the m tournament
    rounds run VMEM-resident.  Returns (B, V) f32."""
    B, V = probs.shape
    vp = -(-V // 128) * 128
    bp = -(-B // block_rows) * block_rows
    probs_p = jnp.zeros((bp, vp), probs.dtype).at[:B, :V].set(probs)
    keys_p = jnp.zeros((bp,), jnp.uint32).at[:B].set(
        keys.astype(jnp.uint32))
    ctx_p = jnp.zeros((bp,), jnp.uint32).at[:B].set(
        ctx_hashes.astype(jnp.uint32))
    out = pl.pallas_call(
        functools.partial(_keyed_kernel, m=m, vocab=V, stream=int(stream)),
        grid=(bp // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, vp), lambda i: (i, 0)),
            pl.BlockSpec((block_rows,), lambda i: (i,)),
            pl.BlockSpec((block_rows,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((block_rows, vp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bp, vp), jnp.float32),
        interpret=interpret,
    )(probs_p, keys_p, ctx_p)
    return out[:B, :V]
