"""The watermark's keyed pseudorandom function, written out for the
reference from its specification (the paper's Alg. 1 streams, the
repository's documented counter hash), in ``jax.numpy`` over uint32.

- ``hash32``: the murmur3 finalizer (shifts 16/15/16, multipliers
  0x7FEB352D and 0x846CA68B);
- ``link(seed, counter) = hash32(seed * G ^ hash32(counter))`` with the
  golden-ratio word G = 0x9E3779B9; ``seed(key, stream, ctx) =
  link(link(key, stream), ctx)``;
- a uniform is ``(link(seed, w) >> 8) / 2^24 + 2^-25``, a g-bit is
  ``link(seed, w) >> 31``;
- the context hash of the last ``c`` tokens is FNV-1a-like: start
  2166136261, per token ``h = (h ^ (t + G + (h << 6) + (h >> 2))) *
  16777619``; positions before the start of the text read token 0.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

G = np.uint32(0x9E3779B9)
DRAFT, TARGET, ACCEPT, PLAIN = 0xD0, 0x7A, 0x5E, 0x99


def hash32(x):
    x = jnp.asarray(x).astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * np.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * np.uint32(0x846CA68B)
    return x ^ (x >> 16)


def link(seed, counter):
    s = jnp.asarray(seed).astype(jnp.uint32)
    return hash32(s * G ^ hash32(jnp.asarray(counter).astype(jnp.uint32)))


def seed(key, stream, ctx):
    return link(link(key, stream), ctx)


def uniform(s, counter):
    bits = link(s, counter)
    return ((bits >> np.uint32(8)).astype(jnp.float32)
            * np.float32(1.0 / (1 << 24)) + np.float32(1.0 / (1 << 25)))


def gbit(s, counter):
    return (link(s, counter) >> np.uint32(31)).astype(jnp.float32)


def context_hashes(tokens: np.ndarray, c: int) -> np.ndarray:
    """(n,) uint32: the hash of the ``c`` tokens before each position of
    ``tokens`` (position i reads tokens[i-c:i], zeros before the start)."""
    t = np.concatenate([np.zeros((c,), np.int64),
                        np.asarray(tokens, np.int64)])
    n = len(tokens)
    with np.errstate(over="ignore"):
        h = np.full((n,), 2166136261, np.uint32)
        for i in range(c):
            tok = t[i:i + n].astype(np.uint32)
            h = h ^ (tok + G + (h << np.uint32(6)) + (h >> np.uint32(2)))
            h = h * np.uint32(16777619)
    return h
