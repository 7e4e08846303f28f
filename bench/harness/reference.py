"""The plain float32 reference: a dense GQA/MHA transformer forward with
no cache and no kernels, every matmul at ``Precision.HIGHEST``, run one
sequence and one layer at a time so that it fits beside the weights.

It follows the published architecture of the configurations (LLaMA
layout): x = E[t]; per layer h = RMSNorm(x), q/k/v projections, rotary
embedding on q and k (half-split rotation, inverse frequencies
theta^(-2i/hd)), causal softmax attention with scale 1/sqrt(hd) and
n_heads / n_kv_heads query heads per key/value head, output projection,
residual; then RMSNorm and the gated SiLU MLP (silu(h W_gate) * h W_in)
W_out, residual; a final RMSNorm and the untied head.

``fp8=True`` is the control: the same forward with every weight matrix
rounded to float8 e4m3 (per output channel scaled), the step below the
configuration's bfloat16.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST
Q_BLOCK = 512


def _fp8(w, axes):
    """Round a weight to float8 e4m3 with one scale per output channel
    (the maximum over the contraction ``axes`` maps to 448)."""
    s = jnp.max(jnp.abs(w), axis=axes, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _w(a, axes, fp8):
    a = a.astype(jnp.float32)
    return _fp8(a, axes) if fp8 else a


def rms_norm(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rope(x, positions, theta):
    """x (S, H, hd); the half-split rotation."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions[:, None].astype(jnp.float32) * inv        # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("dims", "fp8"))
def _layer(blocks, l, x, *, dims, fp8):
    """One layer over one padded sequence x (S, d) in float32."""
    H, Hkv, hd, eps, theta = dims
    S = x.shape[0]
    b = jax.tree.map(lambda a: lax.dynamic_index_in_dim(a, l, 0, False),
                     blocks)
    at, ff = b["attn"], b["ffn"]
    pos = jnp.arange(S)
    h = rms_norm(x, b["ln_attn"].astype(jnp.float32), eps)
    q = jnp.einsum("sd,dhk->shk", h, _w(at["wq"], 0, fp8), precision=HI)
    k = jnp.einsum("sd,dhk->shk", h, _w(at["wk"], 0, fp8), precision=HI)
    v = jnp.einsum("sd,dhk->shk", h, _w(at["wv"], 0, fp8), precision=HI)
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    rep = H // Hkv
    qg = q.reshape(S // Q_BLOCK, Q_BLOCK, Hkv, rep, hd)

    def block(args):
        i, qb = args
        s = jnp.einsum("qgrk,sgk->grqs", qb, k, precision=HI) / np.sqrt(hd)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(qpos[:, None] >= pos[None, :], s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("grqs,sgk->qgrk", a, v, precision=HI)

    o = lax.map(block, (jnp.arange(S // Q_BLOCK), qg)).reshape(S, H, hd)
    x = x + jnp.einsum("shk,hkd->sd", o, _w(at["wo"], (0, 1), fp8),
                       precision=HI)
    h = rms_norm(x, b["ln_ffn"].astype(jnp.float32), eps)
    g = jnp.einsum("sd,df->sf", h, _w(ff["w_gate"], 0, fp8), precision=HI)
    u = jnp.einsum("sd,df->sf", h, _w(ff["w_in"], 0, fp8), precision=HI)
    return x + jnp.einsum("sf,fd->sd", jax.nn.silu(g) * u,
                          _w(ff["w_out"], 0, fp8), precision=HI)


@partial(jax.jit, static_argnames=("fp8",))
def _embed(embed, tokens, *, fp8):
    return _w(embed[tokens], 1, fp8)


@partial(jax.jit, static_argnames=("eps", "fp8"))
def _head(params, x, rows, *, eps, fp8):
    x = rms_norm(x[rows], params["ln_out"].astype(jnp.float32), eps)
    return jnp.einsum("nd,dv->nv", x, _w(params["head"], 0, fp8),
                      precision=HI)


def logits(params: Dict[str, Any], m: Dict[str, Any], tokens: np.ndarray,
           start: int, n: int, n_pad: int, fp8: bool = False):
    """(n_pad, V) float32 logits at positions start .. start + n - 1 of
    ``tokens`` (rows past n repeat the last one)."""
    S = len(tokens)
    S_pad = -(-S // Q_BLOCK) * Q_BLOCK
    toks = np.zeros((S_pad,), np.int32)
    toks[:S] = tokens
    x = _embed(params["embed"], jnp.asarray(toks), fp8=fp8)
    dims = (m["num_attention_heads"], m["num_key_value_heads"],
            m["head_dim"], float(m["rms_norm_eps"]), float(m["rope_theta"]))
    for layer in range(m["num_hidden_layers"]):
        x = _layer(params["blocks"], jnp.int32(layer), x, dims=dims, fp8=fp8)
    rows = np.minimum(start + np.arange(n_pad), start + n - 1)
    return _head(params, x, jnp.asarray(rows, jnp.int32),
                 eps=float(m["rms_norm_eps"]), fp8=fp8)
