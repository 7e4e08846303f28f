"""Random weights from the seed, made by the benchmark in the layout the
program serves (``embed``, ``head``, ``ln_out`` and per-layer stacked
``blocks``), on the device, in the configuration's dtype, by one jitted
call.  The reference reads the same arrays; the program makes none of
them.

Scales keep every layer's output of order one: each projection is drawn
N(0, 1/fan_in) over its contraction width, the embedding N(0, 1) and the
norm gains 1, so the logits come out about N(0, 1) per token.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp


def leaf_shapes(m: Dict[str, Any]) -> List[Tuple[Tuple[str, ...], tuple,
                                                 float]]:
    """(path, shape, std) of every leaf of one model; std 0 marks a norm
    gain (all ones)."""
    d, L = m["hidden_size"], m["num_hidden_layers"]
    H, Hkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    f, V = m["intermediate_size"], m["vocab_size"]
    return [
        (("embed",), (V, d), 1.0),
        (("head",), (d, V), d ** -0.5),
        (("ln_out",), (d,), 0.0),
        (("blocks", "ln_attn"), (L, d), 0.0),
        (("blocks", "ln_ffn"), (L, d), 0.0),
        (("blocks", "attn", "wq"), (L, d, H, hd), d ** -0.5),
        (("blocks", "attn", "wk"), (L, d, Hkv, hd), d ** -0.5),
        (("blocks", "attn", "wv"), (L, d, Hkv, hd), d ** -0.5),
        (("blocks", "attn", "wo"), (L, H, hd, d), (H * hd) ** -0.5),
        (("blocks", "ffn", "w_in"), (L, d, f), d ** -0.5),
        (("blocks", "ffn", "w_gate"), (L, d, f), d ** -0.5),
        (("blocks", "ffn", "w_out"), (L, f, d), f ** -0.5),
    ]


def make_params(m: Dict[str, Any], seed: int, salt: int, dtype):
    """The params of model ``m`` from ``(seed, salt)``, built on the
    default device in one jitted call."""
    leaves = leaf_shapes(m)

    def build(key):
        out: Dict[str, Any] = {}
        for i, (path, shape, std) in enumerate(leaves):
            if std == 0.0:
                x = jnp.ones(shape, dtype)
            else:
                x = (jax.random.normal(jax.random.fold_in(key, i), shape,
                                       jnp.float32) * std).astype(dtype)
            node = out
            for k in path[:-1]:
                node = node.setdefault(k, {})
            node[path[-1]] = x
        return out

    key = jax.random.fold_in(jax.random.key(seed % (2**32)), salt)
    key = jax.random.fold_in(key, seed >> 32)
    return jax.block_until_ready(jax.jit(build)(key))
