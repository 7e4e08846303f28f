"""Fused Gumbel-max watermark decode kernel.

For each row b with seed s_b, computes

    tok_b = argmax_w  log(U_w) / P_w,     U_w = PRF(s_b, w)

with the PRF evaluated *inside* the kernel (murmur-style integer hash —
bit-exact with ``repro.core.prf.kernel_uniform``), so the uniforms never
touch HBM.  HBM traffic is exactly one read of the probs row: the operation
is memory-bound and this is its roofline.

TPU adaptation (vs. the GPU hash-on-host pattern): the whole vocab row
stays resident in VMEM (256k x f32 = 1 MiB << 16 MiB VMEM), the lane dim is
padded to 128, and the block processes ``bm`` rows per grid step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

_MIX = np.uint32(0x9E3779B9)


def _hash_u32(x):
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * np.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * np.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


def _uniform(seed, counter):
    bits = _hash_u32(seed * _MIX ^ _hash_u32(counter))
    # via int32 (exact: the value is < 2^24) — Mosaic has no u32->f32 cast
    return (bits >> np.uint32(8)).astype(jnp.int32).astype(
        jnp.float32) * np.float32(
        1.0 / (1 << 24)) + np.float32(1.0 / (1 << 25))


def _seed_chain(seed, counter):
    """One link of the key -> stream -> context seed chain, in-kernel.

    Bit-exact mirror of ``repro.core.prf._chain``: kernels re-derive the
    per-slot PRF seeds from a per-row uint32 key word resident in VMEM
    (``chain(chain(key, stream), ctx)``) instead of receiving host-derived
    seed tensors."""
    return _hash_u32(jnp.asarray(seed).astype(jnp.uint32) * _MIX
                     ^ _hash_u32(jnp.asarray(counter).astype(jnp.uint32)))


def _kernel(probs_ref, seed_ref, tok_ref, u_ref, *, vocab: int):
    probs = probs_ref[...].astype(jnp.float32)          # (bm, Vp)
    bm, vp = probs.shape
    w = jax.lax.broadcasted_iota(jnp.uint32, (bm, vp), 1)
    seeds = seed_ref[...].astype(jnp.uint32)[:, None]   # (bm, 1)
    u = _uniform(seeds, w)
    # log(U)/P; exclude zero-mass / padded tokens
    score = jnp.log(u) / jnp.maximum(probs, 1e-30)
    valid = (probs > 0) & (w < vocab)
    score = jnp.where(valid, score, -jnp.inf)
    tok = jnp.argmax(score, axis=-1).astype(jnp.int32)  # (bm,)
    tok_ref[...] = tok
    u_ref[...] = jnp.take_along_axis(u, tok[:, None], axis=-1)[:, 0]


def gumbel_argmax_kernel(probs, seeds, *, block_rows: int = 4,
                         interpret: bool = False):
    """probs: (B, V) nonnegative (need not be normalized);
    seeds: (B,) uint32.  Returns (tokens (B,) int32, u (B,) f32)."""
    B, V = probs.shape
    vp = -(-V // 128) * 128
    bp = -(-B // block_rows) * block_rows
    probs_p = jnp.zeros((bp, vp), probs.dtype).at[:B, :V].set(probs)
    seeds_p = jnp.zeros((bp,), jnp.uint32).at[:B].set(
        seeds.astype(jnp.uint32))
    grid = (bp // block_rows,)
    tok, u = pl.pallas_call(
        functools.partial(_kernel, vocab=V),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, vp), lambda i: (i, 0)),
            pl.BlockSpec((block_rows,), lambda i: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows,), lambda i: (i,)),
            pl.BlockSpec((block_rows,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp,), jnp.int32),
            jax.ShapeDtypeStruct((bp,), jnp.float32),
        ],
        interpret=interpret,
    )(probs_p, seeds_p)
    return tok[:B], u[:B]
