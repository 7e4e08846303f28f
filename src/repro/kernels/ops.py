"""Jitted public wrappers for the Pallas kernels.

Dispatch (``kernel_mode``): on a TPU backend every wrapper stages the
Mosaic kernel; on CPU it runs the kernel's bit-exact jnp mirror
(``kernels.ref``); any other backend raises.  The Pallas interpreter runs
only when a caller passes ``interpret=True`` (kernel validation in the
tests and the CPU kernel benchmark).
"""
from __future__ import annotations

from functools import partial

import jax

from repro.core import prf as _prf
from repro.kernels import ref as _ref
from repro.kernels.gumbel_argmax import gumbel_argmax_kernel
from repro.kernels.spec_verify import (spec_verify_kernel,
                                       spec_verify_wm_kernel)
from repro.kernels.ssd import ssd_step_kernel
from repro.kernels.tournament import (tournament_kernel,
                                      tournament_keyed_kernel)
from repro.kernels.wkv import wkv_step_kernel

# default PRF streams of the watermarked verification tail: the ζ^T
# watermark stream, the plain residual/bonus fallback streams (repeated
# contexts), and the finite-m tournament draw stream
DEFAULT_STREAMS = (_prf.STREAM_TARGET, _prf.STREAM_PLAIN + 2,
                   _prf.STREAM_PLAIN + 3,
                   _prf.STREAM_PLAIN + _prf.STREAM_TARGET)


def kernel_mode(interpret: bool | None = None) -> str:
    """How a kernel call runs: ``"interpret"`` when the caller passes
    interpret=True, ``"mosaic"`` on a TPU backend (or when the caller
    passes interpret=False), ``"mirror"`` — the bit-exact jnp reference —
    on CPU.  Any other backend raises: no kernel falls back silently."""
    if interpret is not None:
        return "interpret" if interpret else "mosaic"
    backend = jax.default_backend()
    if backend == "tpu":
        return "mosaic"
    if backend == "cpu":
        return "mirror"
    raise RuntimeError(
        f"no kernel path for backend {backend!r}: the Pallas kernels "
        "compile for TPU (Mosaic) and run their jnp mirrors on CPU")


@partial(jax.jit, static_argnames=("block_rows", "interpret"))
def gumbel_argmax(probs, seeds, *, block_rows: int = 4,
                  interpret: bool | None = None):
    mode = kernel_mode(interpret)
    if mode == "mirror":
        return _ref.gumbel_argmax_ref(probs, seeds)
    return gumbel_argmax_kernel(probs, seeds, block_rows=block_rows,
                                interpret=mode == "interpret")


@partial(jax.jit, static_argnames=("m", "block_rows", "interpret"))
def tournament(probs, seeds, *, m: int = 30, block_rows: int = 4,
               interpret: bool | None = None):
    mode = kernel_mode(interpret)
    if mode == "mirror":
        return _ref.tournament_ref(probs, seeds, m=m)
    return tournament_kernel(probs, seeds, m=m, block_rows=block_rows,
                             interpret=mode == "interpret")


@partial(jax.jit, static_argnames=("stream", "m", "block_rows",
                                   "interpret"))
def tournament_keyed(probs, keys, ctx_hashes, *, stream: int, m: int = 30,
                     block_rows: int = 4, interpret: bool | None = None):
    """Per-row keyed tournament: g-seeds derived in-kernel from the (B,)
    key-word row (multi-tenant batches).  CPU default is the bit-exact jnp
    mirror (``ref.tournament_keyed_ref``)."""
    mode = kernel_mode(interpret)
    if mode == "mirror":
        return _ref.tournament_keyed_ref(probs, keys, ctx_hashes,
                                         stream=stream, m=m)
    return tournament_keyed_kernel(probs, keys, ctx_hashes, stream=stream,
                                   m=m, block_rows=block_rows,
                                   interpret=mode == "interpret")


@partial(jax.jit, static_argnames=("interpret",))
def spec_verify(p, q, draft_tokens, u, resid_seeds, *,
                interpret: bool | None = None):
    mode = kernel_mode(interpret)
    if mode == "mirror":
        return _ref.spec_verify_ref(p, q, draft_tokens, u, resid_seeds)
    return spec_verify_kernel(p, q, draft_tokens, u, resid_seeds,
                              interpret=mode == "interpret")


@partial(jax.jit, static_argnames=("interpret",))
def wkv_step(r, k, v, w, u, s0, *, interpret: bool | None = None):
    """Fused single-step WKV decode (state-in/state-out, no time loop) —
    the fused-for-decode half of the chunk/fused pair (``models.ssm``
    selects chunked-scan for prefill, this for decode).  On TPU this is
    the Mosaic ``wkv_step_kernel``; the CPU default is the bit-exact jnp
    mirror (``ref.wkv_step_ref`` — parity enforced by tests), which is
    also literally one step of the ``wkv_ref`` scan, so decode splices
    bit-exactly onto a chunked-scan prefill prefix."""
    mode = kernel_mode(interpret)
    if mode == "mirror":
        return _ref.wkv_step_ref(r, k, v, w, u, s0)
    return wkv_step_kernel(r, k, v, w, u, s0,
                           interpret=mode == "interpret")


@partial(jax.jit, static_argnames=("interpret",))
def ssd_step(la, dtx, Bf, Cf, h0, *, interpret: bool | None = None):
    """Fused single-step SSD decode — fused-for-decode counterpart of the
    chunked ``ssd`` kernel (same dispatch contract as ``wkv_step``)."""
    mode = kernel_mode(interpret)
    if mode == "mirror":
        return _ref.ssd_step_ref(la, dtx, Bf, Cf, h0)
    return ssd_step_kernel(la, dtx, Bf, Cf, h0,
                           interpret=mode == "interpret")


def _spec_verify_wm_local(p, q, draft_tokens, u, keys, ctx_hashes,
                          seen, live, *, streams, tail,
                          interpret: bool | None):
    """Single-shard body of ``spec_verify_wm`` (grid spans the local batch)."""
    mode = kernel_mode(interpret)
    if mode == "mirror":
        return _ref.spec_verify_wm_ref(p, q, draft_tokens, u, keys,
                                       ctx_hashes, seen, live,
                                       streams=streams, tail=tail)
    return spec_verify_wm_kernel(p, q, draft_tokens, u, keys, ctx_hashes,
                                 seen, live, streams=streams,
                                 tail=tail, interpret=mode == "interpret")


@partial(jax.jit, static_argnames=("interpret", "mesh", "batch_axes",
                                   "tail", "streams"))
def spec_verify_wm(p, q, draft_tokens, u, keys, ctx_hashes, seen,
                   live=None, *, streams=None,
                   interpret: bool | None = None, mesh=None,
                   batch_axes: tuple | None = None, tail=None):
    """Fused watermarked verification tail.  On TPU this stages the Mosaic
    kernel; on CPU the default is the *bit-exact jnp mirror* of the kernel
    program (``ref.spec_verify_wm_ref`` — parity enforced by tests), because
    the Pallas interpreter walks the (B,) grid serially and is ~8x slower
    than the XLA-compiled mirror.  Pass ``interpret=True`` to force the
    interpreter (kernel validation).

    ``keys`` is the (B,) uint32 per-row key-word tensor and ``ctx_hashes``
    the (B, K+1) per-slot context hashes; the per-slot PRF seeds are
    re-derived inside the kernel/mirror from ``streams`` — the static
    ``(wm_stream, plain_resid, plain_bonus, draw_stream)`` tuple (default
    ``DEFAULT_STREAMS``; schemes with a different ζ^T stream pass their
    own).  Mixed-key batches are first-class: the key is data, not a
    compile-time constant.

    ``tail`` is the scheme's ``watermark.base.FusedTail`` declaration
    (static; default = the Gumbel race).  kind="tournament" tails run the
    in-kernel m-round SynthID tournament, drawing the finite-m race coins
    from ``draw_stream``; the 4th output is then the emitted token's
    (B, m) g-bit statistics instead of the (B,) race uniform.

    ``live`` (optional, (B,) bool/int) is the continuous-batching slot
    mask: rows with live == 0 (drained serving slots) skip the whole
    verification/race body (``pl.when``-predicated in the kernel) and
    return all-zero outputs.  None = every row live.

    With ``mesh`` + ``batch_axes`` the call runs under ``shard_map`` over
    the batch dim: every input/output is batch-sharded on ``batch_axes``
    and the kernel's ``grid=(B,)`` spans the *per-shard local* batch — no
    cross-shard communication (the tail is row-independent).  The global
    batch must divide the axes' size."""
    if streams is None:
        streams = DEFAULT_STREAMS
    if mesh is None or not batch_axes:
        return _spec_verify_wm_local(p, q, draft_tokens, u, keys,
                                     ctx_hashes, seen, live,
                                     streams=streams, tail=tail,
                                     interpret=interpret)
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    B, K1 = ctx_hashes.shape
    if live is None:
        live = jnp.ones((B,), jnp.int32)
    spec = P(batch_axes if len(batch_axes) > 1 else batch_axes[0])
    fn = partial(_spec_verify_wm_local, streams=streams, tail=tail,
                 interpret=interpret)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec,) * 8,
                         out_specs=(spec,) * 4, check_vma=False)(
        p, q, draft_tokens, u, keys, ctx_hashes, seen, live)
