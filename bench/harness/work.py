"""Operations and bytes the algorithm needs, counted from shapes: for the
whole step (``mfu``) and for each kernel's roofline share.  Only live
slots, valid prompt tokens and live contexts count, never pool, grid or
padded sizes.

A model ``m`` is a configuration dict (hidden_size, num_hidden_layers,
num_attention_heads, num_key_value_heads, head_dim, intermediate_size,
vocab_size).  A context ``ctx`` is the number of keys a query attends.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

PEAKS = {
    # Google Cloud, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9},
    "TPU v5e": {"flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def layer_params(m: Dict) -> int:
    """Matmul weights of one layer (q, k, v, o and the gated MLP)."""
    d, hd = m["hidden_size"], m["head_dim"]
    H, Hkv = m["num_attention_heads"], m["num_key_value_heads"]
    return d * hd * (2 * H + 2 * Hkv) + 3 * d * m["intermediate_size"]


def forward_flops(m: Dict, tokens: int, ctx_sum: int, head_rows: int) -> int:
    """FLOPs of ``tokens`` query positions through every layer (matmuls and
    attention over ``ctx_sum`` keys in all) plus ``head_rows`` rows of the
    output head."""
    L, H, hd = (m["num_hidden_layers"], m["num_attention_heads"],
                m["head_dim"])
    return (2 * tokens * L * layer_params(m)
            + 4 * L * H * hd * ctx_sum
            + 2 * head_rows * m["hidden_size"] * m["vocab_size"])


def spec_step_flops(t: Dict, d: Dict, K: int, pos: int) -> int:
    """One spec step of one live slot whose target cache holds ``pos``
    positions: the target verifies K+1 fed tokens (with its head on each),
    the draft proposes K (with its head) and re-feeds one more (head
    unused)."""
    tgt = forward_flops(t, K + 1, sum(pos + i + 1 for i in range(K + 1)),
                        K + 1)
    drf = forward_flops(d, K, sum(pos + i + 1 for i in range(K)), K)
    drf += forward_flops(d, 1, pos + K + 1, 0)
    return tgt + drf


def prefill_flops(t: Dict, d: Dict, start: int, n: int) -> int:
    """One prefill chunk of ``n`` valid prompt tokens from ``start``: both
    models over the tokens, the target's head on each."""
    ctx = sum(start + i + 1 for i in range(n))
    return forward_flops(t, n, ctx, n) + forward_flops(d, n, ctx, 0)


def kv_bytes_per_token(m: Dict, dtype_bytes: int) -> int:
    return (2 * m["num_hidden_layers"] * m["num_key_value_heads"]
            * m["head_dim"] * dtype_bytes)


def paged_attention_call(m: Dict, sq: int, ctx: int,
                         dtype_bytes: int) -> Tuple[int, int]:
    """(FLOPs, bytes) that ``sq`` query positions of one slot need from
    paged attention over ``ctx`` keys, in every layer: QK^T and PV, the
    slot's keys and values read once, the queries read and the outputs
    written once."""
    L, H, hd = (m["num_hidden_layers"], m["num_attention_heads"],
                m["head_dim"])
    flops = 4 * L * H * hd * sq * ctx
    byts = (kv_bytes_per_token(m, dtype_bytes) * ctx
            + 2 * L * sq * H * hd * dtype_bytes)
    return flops, byts


def spec_step_attention(t: Dict, d: Dict, K: int, pos: int,
                        dtype_bytes: int) -> Tuple[int, int]:
    """Paged-attention work of one spec step of one live slot: the
    target's verify call over K+1 queries, K draft proposals and the
    draft's re-feed, each reading the keys through its last query."""
    f, b = paged_attention_call(t, K + 1, pos + K + 1, dtype_bytes)
    for i in range(K + 1):
        fi, bi = paged_attention_call(d, 1, pos + i + 1, dtype_bytes)
        f, b = f + fi, b + bi
    return f, b


def prefill_attention(t: Dict, d: Dict, start: int, n: int,
                      dtype_bytes: int) -> Tuple[int, int]:
    f, b = 0, 0
    for m in (t, d):
        fm, bm = paged_attention_call(m, n, start + n, dtype_bytes)
        f, b = f + fm, b + bm
    return f, b


def verify_tail(V: int, K: int, tournament_rounds: int) -> Tuple[int, int]:
    """(FLOPs, bytes) the fused verify tail needs for one live row: read
    the K+1 target and K draft probability rows (float32), and per lane of
    the emitted row's residual about five operations of the race, plus
    about six per tournament round."""
    byts = (2 * K + 1) * V * 4
    flops = V * (5 + 6 * tournament_rounds)
    return flops, byts


def roofline_share(calls: Iterable[Tuple[int, int]], seconds: float,
                   pk: Dict[str, float]) -> Tuple[float, str]:
    """Needed time over measured time, and which bound the needed time
    follows: max(FLOPs / peak FLOP/s, bytes / peak bytes/s) summed over
    the calls."""
    need, by_bytes, by_flops = 0.0, 0.0, 0.0
    for f, b in calls:
        tf, tb = f / pk["flops"], b / pk["hbm_bytes_per_s"]
        need += max(tf, tb)
        by_bytes += tb
        by_flops += tf
    bound = "bytes" if by_bytes >= by_flops else "flops"
    return 100.0 * need / seconds, bound
