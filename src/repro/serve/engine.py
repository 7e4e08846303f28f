"""Batched speculative-decoding engine with watermarking — Algorithm 1.

One ``spec_step`` is the paper's full loop body, as a single jittable
function over fixed shapes:

  1. K sequential draft decode steps, each sampling a *watermarked* draft
     token from ``Q_{ζ^D}`` (Gumbel-max / SynthID / plain);
  2. one batched target verification of the K+1 fed tokens against the
     KV/state cache (attention archs: ``extend_step``; SSM/hybrid archs:
     a sequential scan with per-step state checkpoints for rollback);
  3. accept/reject with **pseudorandom acceptance coins** u = G(ζ^R)
     (Alg. 1 line 8) — or fresh uniforms in ``standard`` mode;
  4. first-rejection residual sampling from the watermarked
     ``(P−Q)_{+,ζ^T}``, bonus token from ``P_{ζ^T}`` when all accepted —
     steps 3–4 run fused in the ``spec_verify_wm`` Pallas kernel (one VMEM
     pass per row: a single (V,) Gumbel race for the emitted extra token,
     or the VMEM-resident m-round SynthID tournament) for every scheme
     that declares a fused tail — dispatch is capability-driven off the
     ``Decoder`` registry (``fused_tail`` / ``draft_sampler`` /
     ``token_stat`` / PRF-stream declarations), never off the watermark
     name;
  5. per-sequence commit: cache positions advance by ``out_len``;
     recurrent states roll back by checkpoint selection.  Every emitted
     slot also records its ``(stat_dim,)`` detection statistics under the
     draft and target streams (``StepOutput.y_draft``/``y_target``), so
     served records feed the detectors without a recovery pass.

``generate`` is device-resident: the multi-step loop, including the
scatter-commit of every step's outputs into preallocated buffers, runs as
one jitted ``while_loop`` with a single host sync per generation (or per
``sync_every`` steps for streaming).

Divergent acceptance is handled with per-sequence cache positions (B,)
throughout — no host-side re-batching.

**Per-slot keys and strength**: the watermark key is engine *data*, not a
global — the state carries a (B,) uint32 key-word row (``keys``) plus a
(B,) strength row (``strength``, the gamma dial: the PRF-gated fraction of
positions that sample from the watermark stream).  Every PRF derivation in
the step chains off its row's key word, so mixed-key batches are
first-class and each slot's stream is bit-identical to a solo run under
its own key (multi-tenant serving — ``serve.keys.KeyPool``).

**Sharded execution** (pass ``mesh=``): the engine state and every output
buffer shard their batch dim over the mesh's dp axes (("pod","data"), via
``sharding.engine_state_specs``); model caches additionally shard kv-heads
/ recurrent channels over "model" — the per-slot key/strength rows shard
with the batch; only scalar step state replicates.  ``jitted_spec_step`` / ``_jitted_gen_loop`` take the mesh plus
explicit in/out shardings, and the fused ``spec_verify_wm`` tail runs its
``grid=(B,)`` on the per-shard *local* batch via ``shard_map`` (the tail is
row-independent, so no collectives are added).  Sharded ``generate`` emits
bit-identical tokens/coins to the single-device path — parity is enforced
by ``tests/test_engine_sharded.py`` on a forced 8-device CPU mesh.

``generate`` also supports chained resume: the returned ``state`` can be
passed back (``generate(..., state=res.state)``) and continues exactly
where the previous call stopped — slot-0 metadata (context hash, coin,
masked flag, detection stats) is carried in the state (``last_ctx``/
``last_u``/``last_msk``/``last_yd``/``last_yt``), never recomputed from
the prompt tail.

**Per-slot stopping / continuous batching**: the loop's stopping condition
is per-sequence — ``n_tokens`` may be a per-slot target vector and
``eos_id`` terminates a slot the moment it emits that token.  Finished
slots *freeze* inside the jitted loop (masked commits, per-slot state
carried unchanged, ``live``-masked rows in the fused verification kernel)
and stop counting toward the AATPS denominators, while the others keep
stepping.  ``serve_requests`` (backed by ``serve.scheduler``) builds
multi-request serving on top: queued prompts are admitted into drained
slots at sync points, with every request's stream bit-identical to a solo
``generate`` run (slot isolation — tests/test_scheduler.py).

Repeated-context masking (Hu et al. 2024): a per-sequence history of used
context hashes; a position whose context was already used samples from the
*raw* distribution with non-watermark randomness, preserving sequence-level
unbiasedness.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import prf
from repro.core import watermark as _wm  # noqa: F401  (register decoders)
from repro.core.watermark.base import (Decoder, FusedTail, get_decoder,
                                       race_argmax, race_draft_sampler)
from repro.kernels import ops as KOPS
from repro.models import model as M
from repro.serve.drafter import (RECURRENT_KEYS, get_drafter,
                                 rollback_checkpoints)
from repro.sharding import rules as SHR

EPS = 1e-30


@dataclasses.dataclass(frozen=True)
class SpecConfig:
    K: int = 4                   # lookahead
    ctx_window: int = 4          # context-hash window c
    temperature: float = 1.0
    watermark: str = "gumbel"    # gumbel | synthid | synthid-inf | none
    m: int = 30                  # synthid tournament rounds
    accept: str = "pseudorandom"  # pseudorandom (Alg. 1) | standard
    mask_repeated: bool = True
    history_cap: int = 1024      # repeated-context history buffer size
    fused: str = "auto"          # auto | on | off — Pallas-fused step tail


def use_fused(scfg: SpecConfig) -> bool:
    """Capability dispatch: the fused Pallas tail runs for every scheme
    whose decoder declares a ``fused_tail`` (Gumbel race, SynthID
    tournament, plain sampling).  ``fused="on"`` raises only for schemes
    with no registered fused tail."""
    if scfg.fused == "off":
        return False
    dec = make_decoder(scfg)
    fusable = dec.fused_tail is not None
    if scfg.fused == "on":
        if not fusable:
            raise ValueError(
                f"fused='on' unsupported for watermark={scfg.watermark!r}: "
                f"decoder {dec.name!r} registers no fused verification "
                "tail (Decoder.fused_tail is None)")
        return True
    return fusable


# kept as the engine-local alias of the shared counter-PRF race (schemes
# and kernels agree bit-exactly on it; see watermark.base.race_argmax)
_race_sample = race_argmax


def _plain_decoder(m: int = 30, **kw) -> Decoder:
    """No watermark: categorical sampling with non-recoverable randomness
    (a Gumbel-max race on offset plain streams, so the fused kernel tail
    can reproduce it from the scalar seed).  The offset streams are part
    of the capability declaration — the engine derives all seeds from
    ``draft_stream``/``target_stream``, never from the scheme name."""
    def dist(probs, key, ctx_hash, stream=0):
        return probs

    def sample(probs, key, ctx_hash, stream=0):
        seed = prf.wm_seed(key, ctx_hash, prf.STREAM_PLAIN + stream + 13)
        return race_argmax(probs, seed), jnp.zeros(())

    def recover(tokens, key, ctx_hashes, stream, vocab):
        return jnp.zeros(tokens.shape, jnp.float32)

    return Decoder(name="none", modified_dist=dist, sample=sample,
                   recover_stats=recover, stat_dim=1, degenerate=False,
                   draft_stream=prf.STREAM_PLAIN + prf.STREAM_DRAFT + 13,
                   target_stream=prf.STREAM_PLAIN + prf.STREAM_TARGET + 13,
                   token_stat=None,
                   fused_tail=FusedTail(kind="race", stat_dim=1),
                   draft_sampler=race_draft_sampler)


def make_decoder(scfg: SpecConfig) -> Decoder:
    """Config → Decoder, uniformly through the registry: every factory
    takes ``m=`` (schemes that don't need it ignore the kwarg), so no
    name-pattern dispatch is left."""
    if scfg.watermark == "none":
        return _plain_decoder(m=scfg.m)
    return get_decoder(scfg.watermark, m=scfg.m)


# ---------------------------------------------------------------------------
# Engine state (a plain dict pytree so it jits/shards cleanly)
# ---------------------------------------------------------------------------

# RECURRENT_KEYS lives in serve.drafter now (re-exported above): the
# drafter registry owns which cache entries need per-step checkpoints.


def key_fingerprint(key) -> str:
    """8-hex-digit fingerprint of a watermark key (any accepted form: a
    python int, a uint32 key word, or a legacy ``jax.random`` key) — tags
    served detection-stat buffers and request results so consumers can
    attribute records to a key without ever seeing key material they
    don't hold."""
    w = np.asarray(jax.device_get(prf.as_key_word(key)))
    return format(int(w), "08x")


def _token_stat_batch(dec: Decoder, seeds, tokens, vocab: int):
    """Detection statistics of committed tokens: ``tokens`` (...,) int32
    with per-slot counter-PRF ``seeds`` (...,) u32 -> (..., stat_dim) f32.
    Schemes without a recoverable statistic (``token_stat is None``)
    record zeros."""
    if dec.token_stat is None:
        return jnp.zeros(tokens.shape + (dec.stat_dim,), jnp.float32)
    fn = lambda sd, tk: dec.token_stat(sd, tk, vocab)   # noqa: E731
    for _ in range(tokens.ndim):
        fn = jax.vmap(fn)
    return fn(seeds, tokens)


def _is_recurrent(cfg: ModelConfig) -> bool:
    return cfg.arch_type in ("ssm", "hybrid")


def strength_gate(keys, ctx_h, strength):
    """The per-position γ gate: a position is watermarked iff its
    STREAM_GAMMA coin falls below the slot's ``strength`` scalar.  True
    means *unwatermarked* (fold into the ``seen``/plain-stream path).
    ``kernel_uniform`` is strictly < 1, so strength = 1.0 gates nothing —
    provably bit-identical to the ungated engine — and strength = 0.0
    gates every position (fully unwatermarked).  Elementwise: ``keys``
    broadcasts as ``(B,)`` or ``(B, 1)`` against any ctx shape."""
    gate_u = prf.uniform_from(keys, ctx_h, prf.STREAM_GAMMA)
    return gate_u >= jnp.asarray(strength, jnp.float32)


def _strength_vec(strength, B: int) -> jnp.ndarray:
    """Normalize the per-slot strength argument (None = fully watermarked,
    scalar, or (B,)) to the (B,) f32 engine-state row."""
    if strength is None:
        return jnp.ones((B,), jnp.float32)
    s = jnp.asarray(strength, jnp.float32)
    return jnp.broadcast_to(s, (B,)) if s.ndim == 0 else s


def first_token_meta(dec: Decoder, scfg: SpecConfig, key, last_logits,
                     window, vocab: int, strength=None) -> Dict[str, Any]:
    """Sample the first (prefill) token from ``last_logits`` (B, V) under
    the context ``window`` (B, c) and derive its slot-0 metadata — the
    shared tail of ``init_state`` and the scheduler's chunked-prefill
    finalize, so the two admission paths are bit-identical by
    construction.  ``key`` may be per-slot ((B,) key words) or a single
    key shared by the batch; ``strength`` (None/scalar/(B,)) applies the
    γ gate to the first position — a gated first token samples from the
    plain stream and is flagged in ``last_msk``."""
    B = window.shape[0]
    keys = prf.as_key_words(key, B)
    sv = _strength_vec(strength, B)
    ctx0 = prf.context_hash(window)
    gate = strength_gate(keys, ctx0, sv)
    p0 = jax.nn.softmax(
        last_logits.astype(jnp.float32) / scfg.temperature, -1)
    first_wm, _ = jax.vmap(
        lambda pr, kw, ch: dec.sample(pr, kw, ch, prf.STREAM_TARGET))(
        p0, keys, ctx0)
    first_pl = jax.vmap(
        lambda pr, kw, ch: race_argmax(
            pr, prf.wm_seed(kw, ch, prf.STREAM_PLAIN + 3)))(p0, keys, ctx0)
    first = jnp.where(gate, first_pl, first_wm).astype(jnp.int32)
    window = jnp.concatenate([window[:, 1:], first[:, None]], axis=1)
    yd_seed = prf.wm_seed(keys, ctx0, prf.STREAM_DRAFT)
    yt_seed = prf.wm_seed(keys, ctx0, prf.STREAM_TARGET)
    return {
        "window": window,          # (B, c) — ends at the pending last token
        "last": first,             # (B,) committed but not yet consumed
        # slot-0 metadata of ``last`` (resume path: never recomputed from
        # the prompt tail) — the context it was sampled under, its recorded
        # acceptance coin, its plain-stream flag, and its detection
        # statistics under the draft/target streams.
        "last_ctx": ctx0,
        "last_u": prf.accept_uniform(keys, ctx0),
        "last_msk": gate,
        "last_yd": _token_stat_batch(dec, yd_seed, first, vocab),
        "last_yt": _token_stat_batch(dec, yt_seed, first, vocab),
    }


def prompt_window(prompts, c: int):
    """The context-hash window of a prompt batch (B, S0) — the last ``c``
    tokens, left-padded with zeros when the prompt is shorter."""
    window = prompts[:, -c:]
    if window.shape[1] < c:
        window = jnp.pad(window, ((0, 0), (c - window.shape[1], 0)))
    return window


def init_state(t_params, d_params, tcfg: ModelConfig, dcfg: ModelConfig,
               scfg: SpecConfig, prompts: jnp.ndarray, max_seq: int, key,
               cache_dtype=None, extras: Optional[Dict[str, Any]] = None,
               strength=None) -> Dict[str, Any]:
    """Prefill both models on ``prompts`` (B, S0) and sample the first token
    from the watermarked target prefill logits.  ``extras`` carries modality
    inputs for the stub frontends ("audio_emb" / "image_emb") — target only;
    the draft is always a text-only LM.

    ``key`` may be a single key (shared by the batch) or per-slot (B,) key
    words; ``strength`` (None/scalar/(B,)) is the per-slot γ operating
    point.  Both become first-class rows of the jitted engine state
    (``keys``/``strength``) — no code path closes over a global key."""
    B, S0 = prompts.shape
    dec = make_decoder(scfg)
    keys = prf.as_key_words(key, B)
    sv = _strength_vec(strength, B)
    t_batch = {"tokens": prompts, **(extras or {})}
    t_logits, t_cache = M.prefill(t_params, tcfg, t_batch,
                                  max_seq, cache_dtype=cache_dtype)
    _, d_cache = get_drafter(dcfg).prefill(d_params, dcfg,
                                           {"tokens": prompts}, max_seq,
                                           cache_dtype=cache_dtype)
    window = prompt_window(prompts, scfg.ctx_window)
    meta = first_token_meta(dec, scfg, keys, t_logits[:, -1], window,
                            tcfg.vocab, strength=sv)
    # gated (plain-sampled) first tokens leave no history entry — their
    # context never consumed watermark randomness
    gated0 = meta["last_msk"]
    hist = jnp.zeros((B, scfg.history_cap), jnp.uint32)
    hist = hist.at[:, 0].set(jnp.where(gated0, 0, meta["last_ctx"]))
    # per-sequence positions from the start (divergent acceptance later)
    t_cache = dict(t_cache, pos=jnp.full((B,), S0, jnp.int32))
    d_cache = dict(d_cache, pos=jnp.full((B,), S0, jnp.int32))
    return {
        "t_cache": t_cache,
        "d_cache": d_cache,
        **meta,
        "keys": keys,              # (B,) per-slot watermark key words
        "strength": sv,            # (B,) per-slot γ operating points
        "n_committed": jnp.full((B,), S0 + 1, jnp.int32),
        "hist": hist,              # (B, H) used context hashes
        "hist_n": (~gated0).astype(jnp.int32),
        "step_idx": jnp.zeros((), jnp.int32),
    }


def init_empty_paged_state(tcfg: ModelConfig, dcfg: ModelConfig,
                           scfg: SpecConfig, batch: int, *, num_pages: int,
                           page_size: int, max_pages: int,
                           max_seq: Optional[int] = None,
                           cache_dtype=None) -> Dict[str, Any]:
    """A zeroed engine state over block-paged KV pools — no prefill has
    happened; every slot starts with an all-null page table (page 0), so
    frozen-slot writes land in the null page and the position gate hides
    them.  The scheduler's chunked admission fills slots in place
    (``Scheduler`` with ``page_size=``): per-slot prompt chunks advance
    ``pos`` through ``extend_step`` and a finalize step samples the first
    token bit-identically to ``init_state``.

    The *target* cache always pages; the draft cache follows its drafter —
    attention drafts join the pool, recurrent drafts keep dense O(1) state
    rows outside it (sized by ``max_seq``, which defaults to the pool's
    logical extent ``max_pages * page_size``)."""
    if _is_recurrent(tcfg):
        raise ValueError(
            f"paged serving needs an attention *target*: arch_type="
            f"{tcfg.arch_type!r} is recurrent (no KV to page).  Recurrent "
            "models are first-class *drafters* — their O(1) state lives "
            "outside the paged pool (serve.drafter) — pass one as dcfg.")
    dec = make_decoder(scfg)
    S = dec.stat_dim
    B = batch
    dtype = cache_dtype or jnp.float32
    t_cache = M.init_paged_cache(tcfg, B, num_pages, page_size, max_pages,
                                 dtype)
    d_cache = get_drafter(dcfg).init_serve_cache(
        dcfg, B, max_seq=max_seq or max_pages * page_size,
        num_pages=num_pages, page_size=page_size, max_pages=max_pages,
        dtype=dtype)
    return {
        "t_cache": t_cache,
        "d_cache": d_cache,
        "window": jnp.zeros((B, scfg.ctx_window), jnp.int32),
        "last": jnp.zeros((B,), jnp.int32),
        "last_ctx": jnp.zeros((B,), jnp.uint32),
        "last_u": jnp.zeros((B,), jnp.float32),
        "last_msk": jnp.zeros((B,), bool),
        "last_yd": jnp.zeros((B, S), jnp.float32),
        "last_yt": jnp.zeros((B, S), jnp.float32),
        "keys": jnp.zeros((B,), jnp.uint32),
        "strength": jnp.ones((B,), jnp.float32),
        "n_committed": jnp.zeros((B,), jnp.int32),
        "hist": jnp.zeros((B, scfg.history_cap), jnp.uint32),
        "hist_n": jnp.zeros((B,), jnp.int32),
        "step_idx": jnp.zeros((), jnp.int32),
    }


def abstract_state(tcfg: ModelConfig, dcfg: ModelConfig, scfg: SpecConfig,
                   batch: int, max_seq: int, cache_dtype=jnp.bfloat16
                   ) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-in of the engine state (dry-run lowering)."""
    t_cache = M.abstract_cache(tcfg, batch, max_seq, cache_dtype)
    d_cache = M.abstract_cache(dcfg, batch, max_seq, cache_dtype)
    t_cache = dict(t_cache, pos=jax.ShapeDtypeStruct((batch,), jnp.int32))
    d_cache = dict(d_cache, pos=jax.ShapeDtypeStruct((batch,), jnp.int32))
    c = scfg.ctx_window
    S = make_decoder(scfg).stat_dim
    sds = jax.ShapeDtypeStruct
    return {
        "t_cache": t_cache,
        "d_cache": d_cache,
        "window": sds((batch, c), jnp.int32),
        "last": sds((batch,), jnp.int32),
        "last_ctx": sds((batch,), jnp.uint32),
        "last_u": sds((batch,), jnp.float32),
        "last_msk": sds((batch,), jnp.bool_),
        "last_yd": sds((batch, S), jnp.float32),
        "last_yt": sds((batch, S), jnp.float32),
        "keys": sds((batch,), jnp.uint32),
        "strength": sds((batch,), jnp.float32),
        "n_committed": sds((batch,), jnp.int32),
        "hist": sds((batch, scfg.history_cap), jnp.uint32),
        "hist_n": sds((batch,), jnp.int32),
        "step_idx": sds((), jnp.int32),
    }


class StepOutput(NamedTuple):
    out_tokens: jnp.ndarray    # (B, K+1) int32, zero-padded past out_len
    out_len: jnp.ndarray       # (B,) int32 in [1, K+1]
    n_accepted: jnp.ndarray    # (B,) int32 in [0, K]
    from_draft: jnp.ndarray    # (B, K+1) bool — 1 = accepted draft token
    u: jnp.ndarray             # (B, K) acceptance coins
    ctx_hashes: jnp.ndarray    # (B, K+1) uint32, per emitted-slot context
    masked: jnp.ndarray        # (B, K+1) bool — repeated-context positions
    y_draft: jnp.ndarray       # (B, K+1, stat_dim) f32 — emitted-token
    #                            detection stats under zeta^D
    y_target: jnp.ndarray      # (B, K+1, stat_dim) f32 — under zeta^T


def abstract_step_output(scfg: SpecConfig, batch: int) -> StepOutput:
    """ShapeDtypeStruct stand-in of a StepOutput (sharded lowering)."""
    sds, K1 = jax.ShapeDtypeStruct, scfg.K + 1
    S = make_decoder(scfg).stat_dim
    return StepOutput(
        out_tokens=sds((batch, K1), jnp.int32),
        out_len=sds((batch,), jnp.int32),
        n_accepted=sds((batch,), jnp.int32),
        from_draft=sds((batch, K1), jnp.bool_),
        u=sds((batch, scfg.K), jnp.float32),
        ctx_hashes=sds((batch, K1), jnp.uint32),
        masked=sds((batch, K1), jnp.bool_),
        y_draft=sds((batch, K1, S), jnp.float32),
        y_target=sds((batch, K1, S), jnp.float32))


# ---------------------------------------------------------------------------
# The speculative step
# ---------------------------------------------------------------------------


def _seen_in_history(hist, hist_n, ctx_h):
    valid = jnp.arange(hist.shape[1])[None, :] < hist_n[:, None]
    return ((hist == ctx_h[:, None]) & valid).any(axis=-1)


def _wm_sample_batch(dec, probs, keys, ctx_h, stream, seen, fallback_stream):
    """Watermarked sample per sequence under per-row key words (B,);
    repeated contexts (and γ-gated positions — both fold into ``seen``)
    fall back to raw categorical sampling (counter-PRF race) with a
    non-watermark stream."""
    tok_wm, _ = jax.vmap(
        lambda pr, kw, ch: dec.sample(pr, kw, ch, stream))(probs, keys,
                                                           ctx_h)

    def raw(pr, kw, ch):
        return _race_sample(pr, prf.wm_seed(kw, ch, fallback_stream))

    tok_raw = jax.vmap(raw)(probs, keys, ctx_h)
    return jnp.where(seen, tok_raw, tok_wm).astype(jnp.int32)


def _gather_probs(probs, tokens):
    """probs (B, V), tokens (B,) -> (B,)"""
    return jnp.take_along_axis(probs, tokens[:, None].astype(jnp.int32),
                               axis=-1)[:, 0]


def _run_target(t_params, tcfg, fed_tokens, t_cache):
    """Run K+1 fed tokens through the target.  Attention archs: one batched
    extend; recurrent archs: sequential scan with state checkpoints.

    Returns (logits (B, K+1, V), new_cache, checkpoints|None) where
    checkpoints maps recurrent cache keys to (K+1, ...) stacked states."""
    if not _is_recurrent(tcfg):
        from repro.models import transformer as T
        logits, cache = T.extend_step(t_params, tcfg, fed_tokens, t_cache)
        return logits, cache, None

    def body(cache, tok):
        logits, cache = M.decode_step(t_params, tcfg, tok, cache)
        chk = {k: cache[k] for k in RECURRENT_KEYS if k in cache}
        return cache, (logits, chk)

    cache, (logits, chks) = jax.lax.scan(body, t_cache, fed_tokens.T)
    return logits.transpose(1, 0, 2), cache, chks


# per-sequence commit with checkpoint selection — shared with the drafter
# API (serve.drafter), which owns rollback semantics for draft caches; the
# target side keeps using it directly for recurrent-target scans.
_rollback = rollback_checkpoints


def make_spec_step(tcfg: ModelConfig, dcfg: ModelConfig, scfg: SpecConfig,
                   mesh=None) -> Callable:
    """Build the jittable spec_step(t_params, d_params, state,
    live=None, eos_id=None) -> (state, StepOutput).  The watermark keys
    and γ strengths are per-slot rows of the state (``state["keys"]`` /
    ``state["strength"]``) — nothing closes over a global key, so
    mixed-key batches are first-class; in ``standard`` accept mode the
    per-row key word also feeds fresh coins.  ``eos_id`` (optional traced
    scalar; -1 disables) truncates the emission — and every piece of
    committed state — at the first EOS token, so a stopped slot's state
    ends exactly at its delivered stream.

    ``live`` (optional, (B,) bool) is the continuous-batching slot mask:
    slots with live == False (drained / free serving slots) are *frozen* —
    the fused verification tail skips their rows, and their per-slot state
    (window / last / history / cache positions / recurrent states) is
    carried through unchanged, so a drained slot's stream can resume or be
    re-admitted bit-exactly while live slots keep stepping.  Live slots
    compute exactly what they would with live=None (slot isolation).

    With ``mesh`` the fused verification tail runs its per-row grid on the
    local batch shard via ``shard_map`` over the mesh's dp axes (the rest
    of the step shards through the caller's in/out shardings + SPMD
    propagation)."""
    dec = make_decoder(scfg)
    K, c = scfg.K, scfg.ctx_window
    temp = scfg.temperature
    fused = use_fused(scfg)
    # the scheme declares which PRF streams its watermarked draws consume
    # ("none" declares offset plain streams; gumbel/synthid the ζ^D/ζ^T
    # base streams) — the engine never branches on the watermark name.
    tail_wm_stream = dec.target_stream
    draft_wm_stream = dec.draft_stream
    tail_spec = dec.fused_tail
    # static PRF-stream tuple for the fused tail: the kernel re-derives
    # per-slot seeds from the key row in VMEM under these streams
    tail_streams = (tail_wm_stream, prf.STREAM_PLAIN + 2,
                    prf.STREAM_PLAIN + 3,
                    prf.STREAM_PLAIN + tail_wm_stream)
    # the draft model's serving behavior — propose / checkpoint / commit —
    # comes from the drafter registry: transformer and recurrent drafts
    # share this one code path with no arch conditionals below.
    drafter = get_drafter(dcfg)

    def _draft_sample_fused(q_full, ctx_h, seen, keys):
        """Scheme-fused draft sampling: the engine derives the per-context
        seed vectors (watermark / finite-m draw / seen-fallback) from the
        per-row key words — elementwise, no vmap — and the scheme's
        ``draft_sampler`` turns them into tokens — a seed-select Gumbel
        race for race schemes, tournament + race for SynthID —
        bit-identical to the two-branch decoder path."""
        wm = prf.wm_seed(keys, ctx_h, draft_wm_stream)
        pl = prf.wm_seed(keys, ctx_h, prf.STREAM_PLAIN + 1)
        if tail_spec is not None and tail_spec.needs_draw_seeds:
            dw = prf.wm_seed(keys, ctx_h,
                             prf.STREAM_PLAIN + draft_wm_stream)
        else:
            dw = wm
        return dec.draft_sampler(q_full, wm, dw, pl, seen)

    def step(t_params, d_params, state, live=None, eos_id=None):
        t_cache, d_cache = state["t_cache"], state["d_cache"]
        window, last = state["window"], state["last"]
        hist, hist_n = state["hist"], state["hist_n"]
        keys, strength = state["keys"], state["strength"]
        B = last.shape[0]
        t_pos0 = t_cache["pos"]
        d_pos0 = d_cache["pos"]

        # ---- 1. draft K tokens sequentially --------------------------------

        def draft_body(carry, _):
            d_cache, cur, window = carry
            logits, d_cache = drafter.propose(d_params, dcfg, cur, d_cache)
            q_full = jax.nn.softmax(logits.astype(jnp.float32) / temp, -1)
            ctx_h = prf.context_hash(window)
            seen = (_seen_in_history(hist, hist_n, ctx_h)
                    if scfg.mask_repeated else jnp.zeros((B,), bool))
            # γ-gated positions fold into ``seen`` before any use: they
            # sample from the plain stream, are flagged ``masked`` and
            # leave no history entry — the strength dial is one mask.
            seen = seen | strength_gate(keys, ctx_h, strength)
            if fused and dec.draft_sampler is not None:
                tok = _draft_sample_fused(q_full, ctx_h, seen, keys)
            else:
                tok = _wm_sample_batch(dec, q_full, keys, ctx_h,
                                       prf.STREAM_DRAFT, seen,
                                       prf.STREAM_PLAIN + 1)
            window = jnp.concatenate([window[:, 1:], tok[:, None]], axis=1)
            chk = drafter.checkpoint(d_cache)
            return (d_cache, tok, window), (tok, q_full, ctx_h, seen, chk)

        with jax.named_scope("spec_draft"):
            (d_cache, _, window_k), \
                (draft_toks, q_fulls, ctx_hs, seens, d_chks) = \
                jax.lax.scan(draft_body, (d_cache, last, window), None,
                             length=K)
        draft_toks = draft_toks.T                       # (B, K)
        q_fulls = q_fulls.transpose(1, 0, 2)            # (B, K, V)
        ctx_hs = ctx_hs.T                               # (B, K)
        seens = seens.T                                 # (B, K)
        # bonus-slot context hash (after d_K)
        ctx_bonus = prf.context_hash(window_k)          # (B,)
        seen_bonus = (_seen_in_history(hist, hist_n, ctx_bonus)
                      if scfg.mask_repeated else jnp.zeros((B,), bool))
        seen_bonus = seen_bonus | strength_gate(keys, ctx_bonus, strength)

        # ---- 2. target verification ----------------------------------------
        with jax.named_scope("spec_target"):
            fed = jnp.concatenate([last[:, None], draft_toks], axis=1)
            t_logits, t_cache, t_chks = _run_target(t_params, tcfg, fed,
                                                    t_cache)
            p_fulls = jax.nn.softmax(t_logits.astype(jnp.float32) / temp, -1)

        with jax.named_scope("spec_tail"):
            # ---- 3. acceptance coins ----------------------------------------
            if scfg.accept == "pseudorandom":
                u = prf.accept_uniform(keys[:, None], ctx_hs)   # (B, K)
            else:
                # fresh coins, still per-slot: each row folds its own key word
                # so mixed-key batches stay slot-isolated even in standard mode
                u = jax.vmap(lambda kw: jax.random.uniform(
                    jax.random.fold_in(jax.random.key(kw), state["step_idx"]),
                    (K,)))(keys)

            all_hashes = jnp.concatenate([ctx_hs, ctx_bonus[:, None]], axis=1)
            all_seen = jnp.concatenate([seens, seen_bonus[:, None]], axis=1)

            if fused:
                # ---- 4. fused verify + residual/bonus (Pallas) --------------
                # The kernel gathers p/q of the drafts, computes the prefix
                # acceptance and samples the single emitted extra token in
                # VMEM — one Gumbel race or one m-round tournament per row,
                # per the scheme's FusedTail declaration — re-deriving every
                # per-slot seed from the (B,) key row under the static
                # ``tail_streams`` and switching to the plain-stream seed on
                # ``seen`` contexts.  No host-derived seed tensors cross HBM.
                axes = SHR.dp_axes(mesh, B) if mesh is not None else None
                live_i = None if live is None else live.astype(jnp.int32)
                n_acc, prefix_i, extra, _ = KOPS.spec_verify_wm(
                    p_fulls, q_fulls, draft_toks, u, keys, all_hashes,
                    all_seen, live_i, streams=tail_streams, tail=tail_spec,
                    mesh=mesh if axes else None, batch_axes=axes)
                prefix = prefix_i.astype(bool)
            else:
                # ---- 4. jnp tail (decoder-generic reference path) -----------
                gather = jax.vmap(_gather_probs, in_axes=(1, 1), out_axes=1)
                p_of_draft = gather(p_fulls[:, :K], draft_toks)   # (B, K)
                q_of_draft = gather(q_fulls, draft_toks)          # (B, K)
                a = jnp.minimum(1.0, p_of_draft / jnp.maximum(q_of_draft, EPS))
                ok = u < a
                prefix = jnp.cumprod(ok.astype(jnp.int32),
                                     axis=-1).astype(bool)
                n_acc = prefix.sum(axis=-1).astype(jnp.int32)  # (B,)
                all_ok = n_acc == K
                # raw (P−Q)_+ rows: the Gumbel race is scale-invariant and
                # the tournament decoder normalizes internally at the
                # padded-lane extent, so no (extent-sensitive) normalization
                # happens here
                resid = jnp.maximum(p_fulls[:, :K] - q_fulls, 0.0)  # (B, K, V)
                resid_toks = jax.vmap(
                    lambda pr, ch, sn: _wm_sample_batch(
                        dec, pr, keys, ch, prf.STREAM_TARGET, sn,
                        prf.STREAM_PLAIN + 2),
                    in_axes=(1, 1, 1), out_axes=1)(resid, ctx_hs, seens)
                bonus_tok = _wm_sample_batch(
                    dec, p_fulls[:, K], keys, ctx_bonus, prf.STREAM_TARGET,
                    seen_bonus, prf.STREAM_PLAIN + 3)             # (B,)
                extra = jnp.where(
                    all_ok, bonus_tok,
                    jnp.take_along_axis(resid_toks,
                                        jnp.minimum(n_acc, K - 1)[:, None],
                                        axis=1)[:, 0])

            # ---- 5. assemble outputs ----------------------------------------
            out = jnp.zeros((B, K + 1), jnp.int32)
            out = out.at[:, :K].set(jnp.where(prefix, draft_toks, 0))
            out = jax.vmap(lambda o, n, e: o.at[n].set(e))(out, n_acc, extra)
            out_len = n_acc + 1
            if eos_id is not None:
                # EOS cut *inside the step*, before the commit: truncate the
                # emission at the first EOS so every piece of committed state
                # (window, last + its metadata, history, cache positions,
                # recurrent rollback) ends exactly at the EOS token — a
                # resumed or re-admitted slot then continues from precisely
                # the delivered stream, never from dropped post-EOS tokens.
                sidx = jnp.arange(K + 1)[None, :]
                is_eos = (out == eos_id) & (sidx < out_len[:, None])
                first = jnp.where(is_eos.any(axis=1),
                                  jnp.argmax(is_eos, axis=1), K + 1)
                out_len = jnp.minimum(out_len, (first + 1).astype(jnp.int32))
                # accepted AND emitted (the drafts dropped by the cut were
                # verified but never delivered)
                n_acc = jnp.minimum(n_acc, out_len)
                out = jnp.where(sidx < out_len[:, None], out, 0)
            from_draft = jnp.arange(K + 1)[None, :] < n_acc[:, None]

        # per-slot detection statistics of the emitted tokens under BOTH
        # candidate streams (what the detectors consume) — O(stat_dim) per
        # token off the counter PRF, so served records need no recovery
        # pass.  Streams here are the detection-time constants, matching
        # ``Decoder.recover_stats`` bit-exactly.
        with jax.named_scope("spec_stats"):
            V = q_fulls.shape[-1]
            yd_seeds = prf.wm_seed(keys[:, None], all_hashes,
                                   prf.STREAM_DRAFT)
            yt_seeds = prf.wm_seed(keys[:, None], all_hashes,
                                   prf.STREAM_TARGET)
            y_d = _token_stat_batch(dec, yd_seeds, out, V)    # (B, K+1, S)
            y_t = _token_stat_batch(dec, yt_seeds, out, V)

        with jax.named_scope("spec_commit"):
            # ---- 6. commit --------------------------------------------------
            t_cache = _rollback(t_cache, t_chks, t_pos0, out_len)
            # draft side: the K-th-token catch-up re-feed + rollback to the
            # accepted length, per the drafter's state semantics (pos-only for
            # KV caches, checkpoint selection for recurrent state).
            with jax.named_scope("spec_draft_refeed"):
                d_cache = drafter.commit(
                    d_params, dcfg, draft_toks[:, K - 1], d_cache, d_chks,
                    d_pos0, out_len)
            # rebuild window/last from the *emitted* tokens
            full = jnp.concatenate([window, out], axis=1)     # (B, c+K+1)
            # the window ending at n'
            idx = out_len[:, None] + jnp.arange(c)[None, :]
            new_window = jnp.take_along_axis(full, idx, axis=1)
            last_i = (out_len - 1)[:, None]
            new_last = jnp.take_along_axis(out, last_i, axis=1)[:, 0]
            # slot-0 metadata for the next buffer (chained-generate resume):
            # the final emitted slot is always the extra (target) token, so
            # only its context hash, recorded coin and seen flag need
            # carrying.
            u_rec = jnp.concatenate([u, jnp.zeros((B, 1), jnp.float32)],
                                    axis=1)
            new_last_ctx = jnp.take_along_axis(all_hashes, last_i,
                                               axis=1)[:, 0]
            new_last_u = jnp.take_along_axis(u_rec, last_i, axis=1)[:, 0]
            new_last_msk = jnp.take_along_axis(all_seen, last_i, axis=1)[:, 0]
            new_last_yd = jax.vmap(lambda y, n: y[n])(y_d, out_len - 1)
            new_last_yt = jax.vmap(lambda y, n: y[n])(y_t, out_len - 1)
            # history append for emitted, previously-unseen contexts — a
            # masked scatter: slot s lands at (hist_n + #adds-before-s) mod
            # H; skipped slots are routed to a trash column that is sliced
            # off.
            if scfg.mask_repeated:
                emitted = jnp.arange(K + 1)[None, :] < out_len[:, None]
                add = emitted & ~all_seen                     # (B, K+1)
                H = hist.shape[1]
                off = jnp.cumsum(add.astype(jnp.int32), axis=1) - add
                pos = jnp.where(add, (hist_n[:, None] + off) % H, H)
                rows = jnp.arange(B)[:, None]
                padded = jnp.concatenate(
                    [hist, jnp.zeros((B, 1), hist.dtype)], axis=1)
                hist = padded.at[rows, pos].set(
                    jnp.where(add, all_hashes, 0))[:, :H]
                hist_n = hist_n + add.sum(axis=1).astype(jnp.int32)

            new_state = dict(state, t_cache=t_cache, d_cache=d_cache,
                             window=new_window, last=new_last,
                             last_ctx=new_last_ctx, last_u=new_last_u,
                             last_msk=new_last_msk,
                             last_yd=new_last_yd, last_yt=new_last_yt,
                             n_committed=state["n_committed"] + out_len,
                             hist=hist, hist_n=hist_n,
                             step_idx=state["step_idx"] + 1)
            if live is not None:
                # Freeze non-live (drained/free) slots: their per-slot state
                # rows revert to the pre-step values so a drained slot can
                # resume or be re-admitted bit-exactly.  KV cache rows need
                # no select — a frozen slot's position does not advance, so
                # the garbage this step wrote beyond ``pos`` is overwritten
                # before it is ever attended (attention is position-gated);
                # recurrent states have no position gate, so they do revert.
                dead = ~live

                def keep0(new, old):      # batch-leading (engine vectors)
                    m = dead.reshape((-1,) + (1,) * (new.ndim - 1))
                    return jnp.where(m, old, new)

                def keep1(new, old):      # (L, B, ...) cache entries
                    m = dead.reshape((1, -1) + (1,) * (new.ndim - 2))
                    return jnp.where(m, old, new)

                for k in ("window", "last", "last_ctx", "last_u", "last_msk",
                          "last_yd", "last_yt", "n_committed", "hist",
                          "hist_n"):
                    new_state[k] = keep0(new_state[k], state[k])
                for cn, state_keys in (("t_cache", RECURRENT_KEYS),
                                       ("d_cache", drafter.state_keys)):
                    cache_new = dict(new_state[cn])
                    cache_new["pos"] = keep0(cache_new["pos"],
                                             state[cn]["pos"])
                    for rk in state_keys:
                        if rk in cache_new:
                            cache_new[rk] = keep1(cache_new[rk], state[cn][rk])
                    new_state[cn] = cache_new
        return new_state, StepOutput(
            out_tokens=out, out_len=out_len, n_accepted=n_acc,
            from_draft=from_draft, u=u, ctx_hashes=all_hashes,
            masked=all_seen, y_draft=y_d, y_target=y_t)

    if mesh is None:
        return step

    def step_on_mesh(*args, **kwargs):
        with SHR.mesh_context(mesh):
            return step(*args, **kwargs)

    return step_on_mesh


# ---------------------------------------------------------------------------
# Recurrent-state checkpoint note: _run_target returns per-step stacked
# recurrent states with layout (steps, L, B, ...) — `_rollback` selects
# per-sequence along the steps axis.
# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# Jit wrappers — single-device (lru-cached) and mesh-aware (explicit in/out
# shardings, memoized on (configs, mesh, abstract shapes, shardings)).
# ---------------------------------------------------------------------------


def _abs_tree(tree):
    """ShapeDtypeStruct skeleton of a pytree of arrays (or of structs)."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x)),
        tree)


def _tree_key(tree) -> Tuple:
    """Hashable signature of a pytree of ShapeDtypeStructs / shardings."""
    if tree is None:
        return (None,)
    flat, treedef = jax.tree_util.tree_flatten(tree)
    leaves = tuple(
        (tuple(leaf.shape), str(leaf.dtype))
        if isinstance(leaf, jax.ShapeDtypeStruct) else leaf
        for leaf in flat)
    return (leaves, treedef)


def state_shardings(state_abs, mesh) -> Dict[str, Any]:
    """NamedShardings for the engine state: caches via the cache rules,
    per-sequence vectors batch-sharded over dp, scalars replicated."""
    B = state_abs["last"].shape[0]
    specs = SHR.engine_state_specs(state_abs, mesh, global_batch=B)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs)


def replicated_shardings(tree, mesh):
    return jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)


@functools.lru_cache(maxsize=64)
def _jitted_spec_step_plain(tcfg: ModelConfig, dcfg: ModelConfig,
                            scfg: SpecConfig) -> Callable:
    return jax.jit(make_spec_step(tcfg, dcfg, scfg))


_SHARDED_JIT_CACHE: Dict[Tuple, Callable] = {}
_SHARDED_JIT_CAP = 64    # mirror the plain path's lru_cache bound


def _sharded_cache_put(memo: Tuple, fn: Callable) -> Callable:
    if len(_SHARDED_JIT_CACHE) >= _SHARDED_JIT_CAP:   # evict oldest
        _SHARDED_JIT_CACHE.pop(next(iter(_SHARDED_JIT_CACHE)))
    _SHARDED_JIT_CACHE[memo] = fn
    return fn


def jitted_spec_step(tcfg: ModelConfig, dcfg: ModelConfig, scfg: SpecConfig,
                     mesh=None, *, state_abs=None, t_shardings=None,
                     d_shardings=None) -> Callable:
    """Configs are frozen dataclasses — cache the jitted step so repeated
    ``generate`` calls don't retrace.

    With ``mesh`` + ``state_abs`` (a ShapeDtypeStruct skeleton of the
    engine state) the step is jitted with explicit in/out shardings: state
    and StepOutput batch-sharded over the dp axes (the per-slot key and
    strength rows ride inside the state and shard with it), and params on
    ``t_shardings``/``d_shardings`` (None = follow the arguments, e.g.
    pre-placed replicated params)."""
    if mesh is None:
        return _jitted_spec_step_plain(tcfg, dcfg, scfg)
    assert state_abs is not None, "sharded jit needs the abstract state"
    memo = ("step", tcfg, dcfg, scfg, mesh, _tree_key(state_abs),
            _tree_key(t_shardings), _tree_key(d_shardings))
    fn = _SHARDED_JIT_CACHE.get(memo)
    if fn is None:
        B = state_abs["last"].shape[0]
        st_sh = state_shardings(state_abs, mesh)
        out_specs = SHR.batch_leading_specs(
            abstract_step_output(scfg, B), mesh, global_batch=B)
        out_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), out_specs)
        fn = jax.jit(
            make_spec_step(tcfg, dcfg, scfg, mesh=mesh),
            in_shardings=(t_shardings, d_shardings, st_sh),
            out_shardings=(st_sh, out_sh))
        _sharded_cache_put(memo, fn)
    return fn


@dataclasses.dataclass
class GenerationResult:
    tokens: np.ndarray          # (B, N) committed tokens (post-prompt)
    lengths: np.ndarray         # (B,) valid lengths
    from_draft: np.ndarray      # (B, N) int8 — 1 = accepted draft token,
    #                             0 = target (first token, residual, bonus)
    u: np.ndarray               # (B, N) coins aligned to emitted slots
    ctx_hashes: np.ndarray      # (B, N) uint32
    masked: np.ndarray          # (B, N) bool
    aatps: float                # average ACCEPTED (draft) tokens per
    #                             *alive* slot-step (drained slots excluded)
    tokens_per_step: float      # delivered tokens per alive slot-step
    #                             (<= aatps + 1; equality without EOS cuts)
    n_steps: int
    state: Optional[Dict[str, Any]] = None   # final engine state (resume)
    eos: Optional[np.ndarray] = None         # (B,) bool — stopped on EOS
    y_draft: Optional[np.ndarray] = None     # (B, N, stat_dim) served
    #                                          detection stats under zeta^D
    y_target: Optional[np.ndarray] = None    # (B, N, stat_dim), zeta^T
    stat_scheme: Optional[str] = None        # decoder name the stats were
    #                                          recorded under (safety tag)
    keys: Optional[np.ndarray] = None        # (B,) uint32 per-slot key
    #                                          words the stats/tokens were
    #                                          generated under
    strength: Optional[np.ndarray] = None    # (B,) f32 per-slot watermark
    #                                          strength (gamma dial)


def _make_gen_loop(tcfg: ModelConfig, dcfg: ModelConfig, scfg: SpecConfig,
                   mesh=None) -> Callable:
    """Device-resident multi-step loop: while any slot is unfinished (and
    the step budget remains), run spec_step and scatter-commit its outputs
    into the preallocated output buffers — no host sync, no per-sequence
    loop.

    Stopping is **per-slot**: each slot b runs until ``lens[b] >=
    n_tokens[b]`` (a per-slot target vector) or until it emits ``eos_id``
    (-1 disables EOS).  A finished slot flips its ``done`` flag and is
    excluded from every subsequent step — its commits are masked, its
    engine state is frozen (``live`` mask into spec_step, so the fused
    verification kernel skips the row), and it stops counting toward the
    AATPS / tokens-per-step denominators (``alive_steps``).  This is the
    sync-point substrate of the continuous-batching scheduler: at loop
    exit, drained slots can be flushed and re-admitted without perturbing
    the surviving slots' streams.

    Each buffer has one trailing trash column; a slot's write position is
    ``lens[b] + s`` when it is a valid emission that still fits, else the
    trash column (sliced off by the caller)."""
    step = make_spec_step(tcfg, dcfg, scfg, mesh=mesh)
    K1 = scfg.K + 1

    def loop(t_params, d_params, carry, n_tokens, eos_id, step_limit):
        cap = carry["toks"].shape[1] - 1   # last column is trash

        def cond(c):
            return (~c["done"]).any() & (c["n_steps"] < step_limit)

        def body(c):
            live = ~c["done"]
            # the step truncates its own emission (and all committed
            # state) at the first EOS, so the commit below just follows
            # out_len; the EOS token itself is the last emitted slot
            state, outp = step(t_params, d_params, c["state"],
                               live=live, eos_id=eos_id)
            B = c["lens"].shape[0]
            idx = jnp.arange(K1)[None, :]
            pos = c["lens"][:, None] + idx
            emitted = (idx < outp.out_len[:, None]) & live[:, None]
            is_eos = emitted & (outp.out_tokens == eos_id)
            valid = emitted & (pos < cap)
            pos = jnp.where(valid, pos, cap)
            rows = jnp.arange(B)[:, None]
            o_u = jnp.concatenate(
                [outp.u, jnp.zeros((B, 1), jnp.float32)], axis=1)

            def commit(buf, vals, fill):
                v = (valid[..., None] if vals.ndim == 3 else valid)
                return buf.at[rows, pos].set(
                    jnp.where(v, vals, fill).astype(buf.dtype))

            lens = c["lens"] + valid.sum(axis=1).astype(jnp.int32)
            eos_hit = c["eos"] | is_eos.any(axis=1)
            alive = live.astype(jnp.int32)
            return dict(
                state=state,
                toks=commit(c["toks"], outp.out_tokens, 0),
                # src flag, matching StepOutput.from_draft: 1 = draft
                fd=commit(c["fd"], outp.from_draft.astype(jnp.int8), 0),
                us=commit(c["us"], o_u, 0.0),
                chs=commit(c["chs"], outp.ctx_hashes, 0),
                msk=commit(c["msk"], outp.masked, False),
                yd=commit(c["yd"], outp.y_draft, 0.0),
                yt=commit(c["yt"], outp.y_target, 0.0),
                lens=lens,
                eos=eos_hit,
                done=c["done"] | eos_hit | (lens >= n_tokens),
                # per-slot efficiency counters over *alive* steps only, so
                # drained slots never dilute AATPS / tokens-per-step
                total=c["total"] + outp.out_len * alive,
                acc_total=c["acc_total"] + outp.n_accepted * alive,
                alive_steps=c["alive_steps"] + alive,
                n_steps=c["n_steps"] + 1,
            )

        return jax.lax.while_loop(cond, body, carry)

    return loop


@functools.lru_cache(maxsize=64)
def _jitted_gen_loop_plain(tcfg: ModelConfig, dcfg: ModelConfig,
                           scfg: SpecConfig) -> Callable:
    return jax.jit(_make_gen_loop(tcfg, dcfg, scfg))


def carry_shardings(carry_abs, mesh) -> Dict[str, Any]:
    """NamedShardings for the generation-loop carry: engine state via the
    state rules, output buffers batch-sharded, counters replicated."""
    B = carry_abs["lens"].shape[0]
    rest = SHR.batch_leading_specs(
        {k: v for k, v in carry_abs.items() if k != "state"},
        mesh, global_batch=B)
    rest_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), rest)
    return dict(rest_sh, state=state_shardings(carry_abs["state"], mesh))


def _jitted_gen_loop(tcfg: ModelConfig, dcfg: ModelConfig, scfg: SpecConfig,
                     mesh=None, *, carry_abs=None, t_shardings=None,
                     d_shardings=None) -> Callable:
    """The jitted generation loop.  With ``mesh`` + ``carry_abs`` it is
    compiled with explicit in/out shardings (carry batch-sharded over dp —
    the per-slot keys/strength ride inside the state — scalar limits
    replicated, params on the given shardings)."""
    if mesh is None:
        return _jitted_gen_loop_plain(tcfg, dcfg, scfg)
    assert carry_abs is not None, "sharded jit needs the abstract carry"
    memo = ("loop", tcfg, dcfg, scfg, mesh, _tree_key(carry_abs),
            _tree_key(t_shardings), _tree_key(d_shardings))
    fn = _SHARDED_JIT_CACHE.get(memo)
    if fn is None:
        c_sh = carry_shardings(carry_abs, mesh)
        rep = NamedSharding(mesh, P())
        fn = jax.jit(
            _make_gen_loop(tcfg, dcfg, scfg, mesh=mesh),
            in_shardings=(t_shardings, d_shardings, c_sh,
                          rep, rep, rep),
            out_shardings=c_sh)
        _sharded_cache_put(memo, fn)
    return fn


def _n_tokens_vec(n_tokens, B: int) -> np.ndarray:
    """Normalize the ``n_tokens`` argument (scalar or per-slot sequence) to
    a (B,) int32 target vector."""
    n_vec = np.asarray(n_tokens, np.int32)
    if n_vec.ndim == 0:
        n_vec = np.full((B,), int(n_vec), np.int32)
    if n_vec.shape != (B,):
        raise ValueError(f"n_tokens must be a scalar or length-{B} "
                         f"sequence, got shape {n_vec.shape}")
    if n_vec.min() < 1:
        raise ValueError(f"n_tokens targets must be >= 1, got {n_vec}")
    return n_vec


def init_gen_carry(state: Dict[str, Any], n_vec: np.ndarray, cap: int,
                   eos_id: Optional[int]) -> Dict[str, Any]:
    """The generation-loop carry over a prepared engine state.

    Slot 0 of each buffer = the pending committed-but-unconsumed token (the
    prefill sample on a fresh state, the previous call's final token on
    resume); its metadata lives in the state.  The extra trailing column
    receives clipped writes.  A slot whose target is already met by the
    pending token — or whose pending token *is* EOS — starts done."""
    B = state["last"].shape[0]
    S = state["last_yd"].shape[-1]
    eos = jnp.int32(-1 if eos_id is None else eos_id)
    eos0 = state["last"] == eos
    return {
        "state": state,
        "toks": jnp.zeros((B, cap + 1), jnp.int32)
                   .at[:, 0].set(state["last"]),
        "fd": jnp.zeros((B, cap + 1), jnp.int8),   # slot 0 is never a draft
        "us": jnp.zeros((B, cap + 1), jnp.float32)
                 .at[:, 0].set(state["last_u"]),
        "chs": jnp.zeros((B, cap + 1), jnp.uint32)
                  .at[:, 0].set(state["last_ctx"]),
        "msk": jnp.zeros((B, cap + 1), bool).at[:, 0].set(state["last_msk"]),
        "yd": jnp.zeros((B, cap + 1, S), jnp.float32)
                 .at[:, 0].set(state["last_yd"]),
        "yt": jnp.zeros((B, cap + 1, S), jnp.float32)
                 .at[:, 0].set(state["last_yt"]),
        "lens": jnp.ones((B,), jnp.int32),
        "eos": eos0,
        "done": eos0 | (jnp.asarray(n_vec) <= 1),
        "total": jnp.zeros((B,), jnp.int32),
        "acc_total": jnp.zeros((B,), jnp.int32),
        "alive_steps": jnp.zeros((B,), jnp.int32),
        "n_steps": jnp.zeros((), jnp.int32),
    }


def generate(t_params, d_params, tcfg: ModelConfig, dcfg: ModelConfig,
             scfg: SpecConfig, prompts, *, n_tokens, key,
             strength=None,
             max_seq: Optional[int] = None,
             extras: Optional[Dict[str, Any]] = None,
             sync_every: Optional[int] = None,
             state: Optional[Dict[str, Any]] = None,
             eos_id: Optional[int] = None,
             mesh=None, shard_params: bool = True) -> GenerationResult:
    """Device-resident generation: run spec steps until every sequence hits
    its target, committing outputs into on-device buffers inside a jitted
    while-loop.  The host is touched once per generation — or once every
    ``sync_every`` steps when set (streaming), at which point partial
    buffers could be flushed to a consumer.

    Stopping is per-sequence: ``n_tokens`` may be a scalar or a length-B
    sequence of per-slot targets, and ``eos_id`` (optional) terminates a
    slot early when it emits that token (the EOS is committed; the slot's
    ``eos`` flag is set in the result).  A finished slot freezes — no
    further commits, no state drift, no contribution to the AATPS /
    tokens-per-step denominators — while the others continue.

    Pass a prebuilt ``state`` to reuse an existing prefill, or the
    ``.state`` of a previous GenerationResult to continue a generation —
    chained calls are bit-identical to one long call (slot-0 metadata comes
    from the state's ``last_ctx``/``last_u``/``last_msk``/``last_yd``/
    ``last_yt``, never from the prompt tail).

    ``key`` may be a python int, a typed jax PRNG key, or a (B,) vector of
    per-slot key words — a *mixed-key batch* is just a (B,) key argument.
    ``strength`` (None / scalar / (B,)) is the per-slot gamma dial: the
    fraction of positions sampled from the watermark stream (1.0 = fully
    watermarked, 0.0 = plain sampling; see ``core.tradeoff``).  Both are
    burned into the engine state at init, so resumed states keep their
    keys.

    Pass ``mesh`` to run the loop sharded: engine state and output buffers
    batch-shard over the dp axes, params shard by the production rules
    (``shard_params=False`` replicates them — e.g. tiny-model parity runs
    on meshes whose axes don't divide the weight dims)."""
    if sync_every is not None and sync_every < 1:
        raise ValueError(f"sync_every must be >= 1, got {sync_every}")
    B, S0 = prompts.shape
    n_vec = _n_tokens_vec(n_tokens, B)
    n_max = int(n_vec.max())
    max_steps = n_max                         # worst case 1 token/step
    # a fast sequence can commit K+1 tokens on every step while the slowest
    # commits 1 — size the cache for the worst case so writes never clip.
    max_seq = max_seq or (S0 + 1 + (scfg.K + 1) * max_steps + 2)
    if state is None:
        state = init_state(t_params, d_params, tcfg, dcfg, scfg, prompts,
                           max_seq, key, extras=extras, strength=strength)

    K1 = scfg.K + 1
    cap = n_max + K1 + 1
    carry = init_gen_carry(state, n_vec, cap, eos_id)
    n_tok = jnp.asarray(n_vec)
    eos = jnp.int32(-1 if eos_id is None else eos_id)
    if mesh is not None:
        t_sh = (SHR.param_shardings(_abs_tree(t_params), mesh)
                if shard_params else replicated_shardings(t_params, mesh))
        d_sh = (SHR.param_shardings(_abs_tree(d_params), mesh)
                if shard_params else replicated_shardings(d_params, mesh))
        loop = _jitted_gen_loop(tcfg, dcfg, scfg, mesh,
                                carry_abs=_abs_tree(carry),
                                t_shardings=t_sh, d_shardings=d_sh)
        t_params = jax.device_put(t_params, t_sh)
        d_params = jax.device_put(d_params, d_sh)
        carry = jax.device_put(carry, carry_shardings(_abs_tree(carry),
                                                      mesh))
        rep = NamedSharding(mesh, P())
        n_tok = jax.device_put(n_tok, rep)
        eos = jax.device_put(eos, rep)
    else:
        loop = _jitted_gen_loop(tcfg, dcfg, scfg)
    if sync_every is None:
        carry = loop(t_params, d_params, carry, n_tok, eos,
                     jnp.int32(max_steps))
    else:
        done = 0
        while done < max_steps:
            done = min(done + sync_every, max_steps)
            carry = loop(t_params, d_params, carry, n_tok, eos,
                         jnp.int32(done))
            if bool(np.asarray(carry["done"]).all()):
                break
    n_steps = int(np.asarray(carry["n_steps"]))
    denom = max(int(np.asarray(carry["alive_steps"]).sum()), 1)
    aatps = int(np.asarray(carry["acc_total"]).sum()) / denom
    tps = int(np.asarray(carry["total"]).sum()) / denom
    return GenerationResult(
        tokens=np.asarray(carry["toks"])[:, :cap],
        lengths=np.asarray(carry["lens"]),
        from_draft=np.asarray(carry["fd"])[:, :cap],
        u=np.asarray(carry["us"])[:, :cap],
        ctx_hashes=np.asarray(carry["chs"])[:, :cap],
        masked=np.asarray(carry["msk"])[:, :cap],
        aatps=float(aatps), tokens_per_step=float(tps), n_steps=n_steps,
        state=carry["state"], eos=np.asarray(carry["eos"]),
        y_draft=np.asarray(carry["yd"])[:, :cap],
        y_target=np.asarray(carry["yt"])[:, :cap],
        stat_scheme=make_decoder(scfg).name,
        keys=np.asarray(carry["state"]["keys"]),
        strength=np.asarray(carry["state"]["strength"]))


def _coerce_serve_config(config, legacy: Dict[str, Any], caller: str):
    """The one-release deprecation shim: ``config=ServeConfig(...)`` is
    the supported surface; the historical ~15 keyword arguments still
    work but fold into a ServeConfig under a DeprecationWarning.  Mixing
    both is ambiguous and raises."""
    import warnings

    from repro.serve.config import LEGACY_KWARGS, ServeConfig

    unknown = sorted(set(legacy) - set(LEGACY_KWARGS))
    if unknown:
        raise TypeError(f"{caller}() got unexpected keyword arguments "
                        f"{unknown} — accepted: config=ServeConfig(...) "
                        f"or the legacy kwargs {list(LEGACY_KWARGS)}")
    if config is not None:
        if legacy:
            raise TypeError(
                f"{caller}() got both config=ServeConfig(...) and legacy "
                f"keyword arguments {sorted(legacy)} — pass one or the "
                "other")
        return config.validate()
    if "batch" not in legacy or "key" not in legacy:
        raise TypeError(f"{caller}() needs config=ServeConfig(batch=..., "
                        "key=..., ...) (or the deprecated batch=/key= "
                        "kwargs)")
    warnings.warn(
        f"passing serving options to {caller}() as individual keyword "
        "arguments is deprecated — construct a serve.ServeConfig and "
        f"pass config=; the kwargs surface is removed next release",
        DeprecationWarning, stacklevel=3)
    return ServeConfig(**legacy).validate()


def serve_requests(t_params, d_params, tcfg: ModelConfig, dcfg: ModelConfig,
                   scfg: SpecConfig, requests, *, config=None,
                   stats_out: Optional[dict] = None, **legacy):
    """Continuous batching: serve a whole request list through
    ``config.batch`` live slots, admitting queued prompts into freed
    slots at sync points of the device-resident loop (see
    ``serve.scheduler``).

    ``config`` is a ``serve.ServeConfig`` — batch/key sizing, the paged
    KV pool knobs, multi-tenant keying and the streaming callbacks, with
    the combo rules validated centrally (``ServeConfig.validate``).  The
    historical individual keyword arguments (``batch=``, ``key=``,
    ``page_size=``, ...) still work for one release via a deprecation
    shim.

    ``requests``: a sequence of ``scheduler.Request``s, ``(prompt,
    n_tokens)`` pairs, or ``{"prompt": ..., "n_tokens": ...}`` dicts —
    admitted FIFO.  ``max_tokens`` / ``max_prompt_len`` size the shared
    buffers (default: the max over the requests).  Returns a list of
    ``scheduler.RequestResult`` in uid (submission) order; each result is
    bit-identical to a solo ``generate()`` of its prompt/key.

    ``page_size`` switches the KV caches to the block-paged pool
    (``num_pages`` pages shared by all slots, prompts admitted in
    ``prefill_chunk``-token chunks between decode sync points).
    ``prefix_cache=True`` (paged mode only) additionally shares
    identical full-page prompt prefixes across requests: repeated system
    prompts keep one resident KV copy, admissions that hit skip the
    shared prefix's prefill, and the scheduler's event log records each
    hit as ``("admit_shared", uid, n_cached_tokens)``.  Results stay
    bit-identical to solo ``generate()`` — KV pages depend only on
    prompt tokens and weights, never on the per-slot watermark keys.

    ``key_pool`` (a ``serve.keys.KeyPool``) turns on multi-tenant keying:
    each request is served under its own per-slot key word (explicit
    ``Request.key`` or pool-assigned with refcounted rotation), and
    ``strength_controller`` (``serve.keys.StrengthController``) maps each
    request's ``tier`` to a watermark-strength gamma on the paper's
    strength/efficiency Pareto curve (``core.tradeoff``).  Without a pool
    every request serves under ``key`` at full strength — bit-identical to
    the single-tenant engine.

    Streaming & overlap: ``on_token(uid, token, meta)`` fires as tokens
    surface at sync points (``on_result(RequestResult)`` per flushed
    request); ``overlap=True`` double-buffers the loop — the next decode
    chunk dispatches before the round's host work, hiding flush/admission
    behind device compute at a one-chunk token-visibility latency (served
    bits unchanged; see ``docs/serving.md``).  Per-request TTFT and
    inter-token gaps land on every ``RequestResult``; pass ``stats_out={}``
    to receive the scheduler's aggregate ``stats()`` (TTFT/gap means,
    prefix-cache hit/saved/eviction counters, page-pool peaks).  For an
    async-iterator surface use ``serve_stream``.
    """
    from repro.serve.scheduler import Scheduler, as_request

    config = _coerce_serve_config(config, legacy, "serve_requests")
    reqs = [as_request(r) for r in requests]
    if not reqs:
        return []
    config = config.resolved(reqs)
    sched = Scheduler(t_params, d_params, tcfg, dcfg, scfg,
                      **config.scheduler_kwargs())
    sched.submit_many(reqs)
    results = sched.run()
    if stats_out is not None:
        stats_out.update(sched.stats())
    return results


async def serve_stream(t_params, d_params, tcfg: ModelConfig,
                       dcfg: ModelConfig, scfg: SpecConfig, requests, *,
                       config=None, stats_out: Optional[dict] = None,
                       **legacy):
    """Async-iterator variant of ``serve_requests``: yields ``(uid,
    token, step_meta)`` as slots progress, awaiting between sync rounds
    so other coroutines (response writers, new-request intake) interleave
    with serving.  Takes the same ``config: serve.ServeConfig`` (legacy
    kwargs deprecated, as in ``serve_requests``) — but here ``overlap``
    defaults on: a streaming consumer is latency-shaped, and the
    double-buffered loop hides host work behind the in-flight chunk
    (pass ``overlap=False`` for the strict sequential schedule, e.g. on
    paged pools sized without the doubled growth horizon).  Completed
    ``RequestResult``s arrive through ``on_result`` (fired at each
    flush) and aggregate timing/cache counters through ``stats_out``, as
    in ``serve_requests``; the yielded token streams are bit-identical
    to those drained results."""
    import asyncio

    from repro.serve.scheduler import Scheduler, as_request

    if config is None and "overlap" not in legacy and legacy:
        legacy = dict(legacy, overlap=True)  # streaming's historical default
    config = _coerce_serve_config(config, legacy, "serve_stream")
    reqs = [as_request(r) for r in requests]
    if not reqs:
        return
    config = config.resolved(reqs)
    sched = Scheduler(t_params, d_params, tcfg, dcfg, scfg,
                      **config.scheduler_kwargs())
    sched.submit_many(reqs)
    last_round = 0
    for ev in sched.run_stream():
        yield ev
        if ev[2]["round"] != last_round:
            last_round = ev[2]["round"]
            await asyncio.sleep(0)
    if stats_out is not None:
        stats_out.update(sched.stats())
