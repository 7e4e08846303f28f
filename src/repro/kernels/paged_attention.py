"""Pallas paged-decode attention: GQA decode against a block-paged KV pool.

The serving KV cache is a fixed pool of ``num_pages`` pages of
``page_size`` token slots each — ``(num_pages, Hkv, page_size, hd)`` per
layer, so one kv-head's page is a contiguous ``(page_size, hd)`` tile —
plus a per-slot **page table** ``(B, max_pages)`` of physical page ids
mapping a slot's logical positions ``[p*page_size, (p+1)*page_size)`` to
pool rows.  Page 0 is the reserved *null* page: table tails point at it,
and writes routed there (freed slots, clamped overflow) land in garbage
that the position gate below never attends.

The kernel runs a ``(B, Hkv, n_pages)`` grid, pages innermost.  The page
table and per-slot positions are **scalar-prefetched**
(``pltpu.PrefetchScalarGridSpec``) so each k/v page block is DMA'd straight
from its table-selected pool row into VMEM — the gather is the block
index_map, no dense (B, S, Hkv, hd) cache is ever materialized.  Per
(batch, kv-head) the n_rep query heads of the group (times the Sq query
positions) form the rows of one online-softmax accumulation over the
pages: running max, running sum and an (rows, hd) f32 accumulator in VMEM
scratch, so VMEM use does not grow with the context.  Pages past a
slot's last attended position are neither fetched (the index_map clamps
to the last live page, and a repeated block index issues no DMA) nor
computed: every lane there is masked, and a masked lane contributes an
exact zero to the mirror's softmax too.

Numerics contract: the jnp mirror (``ref.paged_attention_ref``) is the
page-table gather + the dense ``decode_attention`` math (one softmax over
the full gathered extent) and stays the CPU serving path.  The kernel's
page-wise online softmax computes the same function in a different
operation order, so it agrees with the mirror to rounding, not bit for bit
(``tests/test_paged_attention.py`` holds it to the mirror; the chip smoke
reports the error at bf16).

TPU layout: the k/v blocks are full ``(page_size, hd)`` tiles and the q/out
blocks full ``(Sq * n_rep, hd)`` tiles, so any page size and head layout
satisfies the (8, 128) block rule; ``page_size`` a multiple of 16 and
``hd`` a multiple of 128 keep bf16 tiles unpadded.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _paged_attn_body(tbl_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                     m_ref, l_ref, acc_ref, *, n_pages: int, page_size: int,
                     n_rep: int, sq: int):
    """Grid (B, Hkv, n_pages), pages innermost.  One online-softmax update
    per live page; the last page step normalizes and writes the rows."""
    b = pl.program_id(0)
    p = pl.program_id(2)
    rows, hd = q_ref.shape[-2:]

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    # query row r = qi * n_rep + g attends logical kv positions < pos + qi
    # (the table maps logical page p -> physical pool row)
    pos = pos_ref[b]

    @pl.when(p * page_size < pos + sq - 1)
    def _page():
        q = q_ref[0, 0]                                   # (rows, hd)
        k = k_ref[0, 0]                                   # (page_size, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * (1.0 / math.sqrt(hd))
        kv_pos = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (rows, page_size), 1)
        q_off = jax.lax.broadcasted_iota(
            jnp.int32, (rows, page_size), 0) // n_rep
        s = jnp.where(kv_pos < pos + q_off, s, jnp.finfo(jnp.float32).min)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        e = jnp.exp(s - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(e, axis=-1, keepdims=True)
        v = v_ref[0, 0]
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            e.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(p == n_pages - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def paged_attention_kernel(q, k_pool, v_pool, page_table, pos, *,
                           interpret: bool = False):
    """q (B,Sq,H,hd); pools (P,Hkv,page_size,hd); page_table (B,max_pages)
    int32 physical page ids (0 = null); pos () or (B,) — query i attends
    logical kv positions < pos + i (``decode_attention`` semantics), so
    pos >= 1.  Returns (B,Sq,H,hd) in the pool dtype."""
    B, Sq, H, hd = q.shape
    _, Hkv, page_size, _ = k_pool.shape
    n_rep = H // Hkv
    rows = Sq * n_rep
    n_pages = page_table.shape[1]
    pos_b = jnp.broadcast_to(jnp.atleast_1d(pos), (B,)).astype(jnp.int32)
    # (B, Sq, Hkv, n_rep, hd) -> (B, Hkv, Sq * n_rep, hd): one kv-head
    # group's query rows form one contiguous tile
    qg = q.reshape(B, Sq, Hkv, n_rep, hd).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(B, Hkv, rows, hd).astype(k_pool.dtype)

    def kv_page(b, h, p, tbl, ps):
        # the page-table gather: logical page p of slot b comes from pool
        # row tbl[b, p]; pages past the last attended position repeat the
        # last live page (no new DMA) and are skipped in the body
        last = jnp.maximum(ps[b] + Sq - 2, 0) // page_size
        return (tbl[b, jnp.minimum(p, last)], h, 0, 0)

    body = partial(_paged_attn_body, n_pages=n_pages, page_size=page_size,
                   n_rep=n_rep, sq=Sq)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, n_pages),
        in_specs=[
            pl.BlockSpec((1, 1, rows, hd),
                         lambda b, h, p, tbl, ps: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, page_size, hd), kv_page),
            pl.BlockSpec((1, 1, page_size, hd), kv_page),
        ],
        out_specs=pl.BlockSpec((1, 1, rows, hd),
                               lambda b, h, p, tbl, ps: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        body, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, rows, hd), v_pool.dtype),
        name="paged_attention",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(page_table.astype(jnp.int32), pos_b, qg, k_pool, v_pool)
    out = out.reshape(B, Hkv, Sq, n_rep, hd).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, Sq, H, hd)


@partial(jax.jit, static_argnames=("window", "grouped", "interpret"))
def paged_decode_attention(q, k_pool, v_pool, page_table, pos, *,
                           window: int = 0, grouped: bool = False,
                           interpret: bool | None = None):
    """Public paged-decode attention, dispatched by ``ops.kernel_mode``:
    the Mosaic kernel on TPU, the jnp mirror (``ref.paged_attention_ref``
    = page-table gather + the dense ``decode_attention`` math) on CPU,
    the Pallas interpreter only when ``interpret=True``.

    ``window`` (sliding-window attention) always takes the mirror — the
    kernel covers the full-extent serving decode path.  ``grouped`` (the
    sequence-sharded GQA softmax layout of the dense path) is the same
    function, so the kernel serves it too."""
    from repro.kernels import ops, ref
    from repro.sharding.rules import _ambient_mesh
    mode = ops.kernel_mode(interpret)
    if window or mode == "mirror":
        return ref.paged_attention_ref(q, k_pool, v_pool, page_table, pos,
                                       window=window, grouped=grouped)
    kernel = partial(paged_attention_kernel, interpret=mode == "interpret")
    mesh = _ambient_mesh()
    if mesh is None or mesh.size == 1:
        return kernel(q, k_pool, v_pool, page_table, pos)
    return _shard_mapped(kernel, mesh, q, k_pool, v_pool, page_table, pos)


def _shard_mapped(kernel, mesh, q, k_pool, v_pool, page_table, pos):
    """Run the kernel per device under ``shard_map`` (a custom call cannot
    be partitioned by the SPMD pass): batch rows over the dp axes that
    divide B, kv-heads and their query groups over "model" when Hkv
    divides it.  A single shared kv-head (MQA) replicates the pool and
    still splits the query heads; any other split replicates the heads."""
    from jax.sharding import PartitionSpec as P

    from repro.sharding.rules import dp_axes
    B, _, H, _ = q.shape
    Hkv = k_pool.shape[1]
    m = mesh.shape["model"] if "model" in mesh.axis_names else 1
    dp = dp_axes(mesh, B)
    bs = (dp if len(dp) > 1 else dp[0]) if dp else None
    hk = "model" if m > 1 and Hkv % m == 0 else None
    hq = "model" if m > 1 and H % m == 0 and (hk or Hkv == 1) else None
    pos_b = jnp.broadcast_to(jnp.atleast_1d(pos), (B,)).astype(jnp.int32)
    pool = P(None, hk, None, None)
    return jax.shard_map(
        kernel, mesh=mesh,
        in_specs=(P(bs, None, hq, None), pool, pool, P(bs, None), P(bs)),
        out_specs=P(bs, None, hq, None), check_vma=False)(
        q, k_pool, v_pool, page_table, pos_b)
