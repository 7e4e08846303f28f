"""Continuous-batching scheduler over the device-resident generation loop.

The engine's jitted while-loop (``engine._make_gen_loop``) already stops
per-slot (per-slot ``n_tokens`` targets + EOS) and freezes finished slots
(masked commits, frozen per-slot state, ``live``-masked ``spec_verify_wm``
rows).  This module adds the multi-request serving layer on top:

- a FIFO **request queue** (admission order == submission order);
- a per-slot **lifecycle** FREE → PREFILLING → DECODING → DRAINED → FREE;
- **admission at sync points**: every ``sync_every`` engine steps the loop
  returns to the host; drained slots are flushed (a per-slot slice of the
  output/detection buffers — no full all-gather) and queued prompts are
  prefilled into the freed slots of the *live* batch state (a batch-1
  prefill scattered into slot ``b`` of every state/buffer row).

Passing ``page_size=`` switches the KV caches from dense ``(L, B,
max_seq, ...)`` rectangles to the **block-paged pool** (``num_pages``
fixed pages shared by every slot; per-slot page tables; see
``models.transformer.init_paged_cache`` and ``docs/serving.md``).  Slots
then decouple from memory: a slot holds only the pages its committed
prefix needs (grown incrementally at sync points), so ``batch`` can far
exceed what dense worst-case rows would fit.  Admission also changes:
prompts prefill in fixed ``prefill_chunk``-token chunks, **one chunk per
sync round**, interleaved with the decode loop — a giant prompt cannot
stall the continuous batch, and every admission compiles exactly one
chunk-shaped ``extend_step`` instead of one prefill per distinct prompt
length.  The slot-isolation contract is unchanged and still enforced
bit-exactly against dense solo ``generate()``.

``prefix_cache=True`` (paged mode only) additionally shares identical
prompt prefixes *across* requests: the allocator is refcounted, and a
``PrefixCache`` keyed by chain digests over page-aligned token blocks
lets admission point a new slot's table at already-resident pages for
every full-page prefix hit, chunk-prefilling only the uncached tail.
KV pages are a pure function of prompt tokens + weights — never of the
per-slot watermark key/strength rows — so sharing is sound across
tenants and keeps every request bit-identical to its solo
``generate()``.

The correctness contract is **slot isolation**: a request's committed
tokens, provenance flags (``src``), acceptance coins, context hashes and
repeated-context masks are bit-identical to a solo ``engine.generate()``
run of the same prompt/key, regardless of what is admitted or drained in
the other slots (enforced by ``tests/test_scheduler.py`` on both the
single-device and the forced-multi-device mesh paths).  It holds because
every per-slot quantity (watermark streams, history, caches) is a function
of the slot's own state — including its own row of the engine's per-slot
``keys``/``strength`` tensors — which also means it requires
``accept="pseudorandom"`` (Alg. 1): ``standard`` accept coins draw from
the *global* step index and would entangle slots.

**Multi-tenant keys** (pass ``key_pool=`` a ``serve.keys.KeyPool``): each
request is admitted under its own watermark key word — an explicit
``Request.key``, or the pool's least-loaded active word (refcounted;
released at flush; ``rotate()`` epochs retire words for new admissions
while in-flight ones drain).  A ``strength_controller``
(``serve.keys.StrengthController``) maps ``Request.tier`` to a
per-request gamma on the strength/efficiency trade-off curve.  Results
carry the key's 8-hex fingerprint (never key material) for detection
attribution.  Without a pool every request serves under the scheduler's
``key`` at gamma 1.0 — bit-identical to the single-tenant scheduler.

Typical use goes through ``engine.serve_requests()``::

    results = E.serve_requests(tp, dp, tcfg, dcfg, scfg, requests,
                               batch=8, key=key, max_tokens=128,
                               eos_id=0, sync_every=8)

or, incrementally::

    sched = Scheduler(tp, dp, tcfg, dcfg, scfg, batch=8, key=key,
                      max_tokens=128)
    for prompt in prompts:
        sched.submit(prompt, n_tokens=64)
    results = sched.run()
"""
from __future__ import annotations

import dataclasses
import hashlib
import time
from collections import OrderedDict, deque
from typing import (Any, Callable, Deque, Dict, Iterator, List, Optional,
                    Sequence, Set, Tuple)

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import prf
from repro.serve import engine as E
from repro.serve.config import ServeConfig
from repro.serve.drafter import get_drafter, paged_slot_extend

# ---------------------------------------------------------------------------
# Slot lifecycle
# ---------------------------------------------------------------------------

FREE = "FREE"                # no request; done-masked in the loop
PREFILLING = "PREFILLING"    # batch-1 prefill being scattered into the slot
DECODING = "DECODING"        # live in the jitted loop
DRAINED = "DRAINED"          # finished (target/EOS); awaiting flush

PHASES = (FREE, PREFILLING, DECODING, DRAINED)


@dataclasses.dataclass
class Request:
    """One prompt to serve.  ``n_tokens`` counts post-prompt tokens
    (including the prefill sample), exactly like ``generate()``.

    ``key`` (optional) pins the watermark key word this request is served
    under (any form ``prf.as_key_word`` accepts); ``tier`` (optional)
    names a strength class for the scheduler's ``StrengthController``
    ("latency"/"balanced"/"assurance" by default)."""
    prompt: np.ndarray
    n_tokens: int
    uid: int = -1
    key: Optional[int] = None
    tier: Optional[str] = None


_REQUEST_FIELDS = ("prompt", "n_tokens", "uid", "key", "tier")


def as_request(r) -> Request:
    """Normalize the accepted intake formats — a ``Request``, a
    ``{"prompt": ..., "n_tokens": ..., ["uid"/"key"/"tier"]}`` dict, or a
    ``(prompt, n_tokens)`` pair — to a ``Request`` (the single parser
    shared by ``Scheduler.submit_many`` and ``engine.serve_requests``).
    Unknown dict fields raise: a silently dropped ``key`` would serve a
    request under the wrong watermark key."""
    if isinstance(r, Request):
        return r
    if isinstance(r, dict):
        unknown = sorted(set(r) - set(_REQUEST_FIELDS))
        if unknown:
            raise ValueError(f"unknown request fields {unknown} — "
                             f"accepted: {list(_REQUEST_FIELDS)}")
        return Request(prompt=np.asarray(r["prompt"], np.int32),
                       n_tokens=int(r["n_tokens"]),
                       uid=int(r.get("uid", -1)),
                       key=r.get("key"), tier=r.get("tier"))
    return Request(prompt=np.asarray(r[0], np.int32), n_tokens=int(r[1]))


@dataclasses.dataclass
class RequestResult:
    """Per-request output, truncated to the committed length.  The arrays
    are bit-identical to a solo ``generate()`` of the same prompt/key."""
    uid: int
    tokens: np.ndarray        # (n,) committed tokens (post-prompt)
    src: np.ndarray           # (n,) int8 — 1 = accepted draft token
    u: np.ndarray             # (n,) acceptance coins aligned to slots
    ctx_hashes: np.ndarray    # (n,) uint32
    masked: np.ndarray        # (n,) bool repeated-context flags
    length: int
    eos: bool                 # stopped on eos_id (EOS token committed)
    alive_steps: int          # engine steps this request was live for
    n_accepted: int           # accepted draft tokens over those steps
    n_emitted: int            # emitted tokens over those steps
    y_draft: Optional[np.ndarray] = None    # (n, stat_dim) served zeta^D
    #                                         detection statistics
    y_target: Optional[np.ndarray] = None   # (n, stat_dim), zeta^T
    stat_scheme: Optional[str] = None       # decoder the stats belong to
    key_word: Optional[int] = None          # uint32 watermark key word the
    #                                         request was served under
    strength: float = 1.0                   # gamma the request ran at
    tier: Optional[str] = None              # strength class, when given
    ttft_s: Optional[float] = None          # submit -> first token visible
    #                                         at a sync point (host wall)
    arrivals_s: Optional[np.ndarray] = None  # (n,) per-token visibility
    #   times relative to submit; tokens surfacing in the same sync round
    #   share a timestamp, so gaps within a round are 0

    @property
    def gaps_s(self) -> Optional[np.ndarray]:
        """Inter-token gaps (n-1,); non-negative by construction."""
        if self.arrivals_s is None or len(self.arrivals_s) < 2:
            return None
        return np.diff(self.arrivals_s)

    @property
    def key_fingerprint(self) -> Optional[str]:
        """8-hex fingerprint of the serving key (what logs/replays carry —
        never key material)."""
        if self.key_word is None:
            return None
        return format(int(np.uint32(self.key_word)), "08x")

    @property
    def aatps(self) -> float:
        return self.n_accepted / max(self.alive_steps, 1)

    @property
    def tokens_per_step(self) -> float:
        return self.n_emitted / max(self.alive_steps, 1)

    def as_generation_result(self) -> E.GenerationResult:
        """A batch-1 ``GenerationResult`` view, so the detection pipeline
        (``pipeline.records_from_generation``) consumes scheduler output
        unchanged — including the served detection-stat buffers and the
        per-slot key/strength rows the served-stat gate checks."""
        kw = None if self.key_word is None else \
            np.array([self.key_word], np.uint32)
        return E.GenerationResult(
            tokens=self.tokens[None], lengths=np.array([self.length]),
            from_draft=self.src[None], u=self.u[None],
            ctx_hashes=self.ctx_hashes[None], masked=self.masked[None],
            aatps=self.aatps, tokens_per_step=self.tokens_per_step,
            n_steps=self.alive_steps, eos=np.array([self.eos]),
            y_draft=None if self.y_draft is None else self.y_draft[None],
            y_target=None if self.y_target is None else self.y_target[None],
            stat_scheme=self.stat_scheme, keys=kw,
            strength=np.array([self.strength], np.float32))


@dataclasses.dataclass
class _Slot:
    phase: str = FREE
    request: Optional[Request] = None


class PageAllocator:
    """Host-side **refcounted** free-list allocator over the physical KV
    page pool.

    Page 0 is the reserved **null page**: it is never handed out, and an
    all-zero page-table row aliases every logical page to it — so freed
    slots (whose frozen loop iterations still write k/v) scribble into
    garbage no reader ever attends, instead of into pages that may have
    been reallocated to a new request.  The allocatable set is therefore
    ``{1, .., num_pages - 1}``.

    Refcounts let multiple readers hold the same physical page (prefix
    sharing): ``alloc`` hands out pages at refcount 1, ``share`` takes an
    extra reference on a held page, and ``free`` *decrements* — a page
    returns to the free list only when its last reference drops.  Shared
    pages are read-only by construction (only completely written prompt
    pages are ever shared; decode appends at ``pos >= S0`` and rollback
    is pos-only), so no copy-on-write is needed.
    """

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError(f"num_pages must be >= 2 (page 0 is the "
                             f"reserved null page), got {num_pages}")
        self.num_pages = num_pages
        # stored descending so pop() hands out ascending ids (stable,
        # test-friendly); correctness never depends on the order
        self._free = list(range(num_pages - 1, 0, -1))
        self._refs: Dict[int, int] = {}       # page -> refcount (>= 1)
        self.n_used_peak = 0                  # high-water mark of n_used

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_used(self) -> int:
        return len(self._refs)

    def refcount(self, page: int) -> int:
        """Current reference count of ``page`` (0 if free)."""
        return self._refs.get(int(page), 0)

    def alloc(self, n: int) -> List[int]:
        """Take ``n`` pages off the free list at refcount 1; raises
        ``RuntimeError`` on exhaustion (never hands out the null page or
        a held page twice)."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            raise RuntimeError(
                f"KV page pool exhausted: need {n} pages, "
                f"{len(self._free)} of {self.num_pages - 1} free")
        out = [self._free.pop() for _ in range(n)]
        for p in out:
            self._refs[p] = 1
        self.n_used_peak = max(self.n_used_peak, len(self._refs))
        return out

    def share(self, page: int) -> int:
        """Take one more reference on an already-held page (prefix-cache
        hit pointing a new slot's table at it); the null page and free /
        foreign ids raise."""
        page = int(page)
        if page not in self._refs:
            raise ValueError(f"sharing page {page} that is not allocated "
                             "(free, null page, or foreign id)")
        self._refs[page] += 1
        return self._refs[page]

    def free(self, pages: Sequence[int]) -> None:
        """Drop one reference per page; a page returns to the free list
        when its count hits 0.  Over-frees and foreign ids raise."""
        for p in pages:
            p = int(p)
            if p not in self._refs:
                raise ValueError(f"freeing page {p} that is not allocated "
                                 "(double free, null page, or foreign id)")
            self._refs[p] -= 1
            if self._refs[p] == 0:
                del self._refs[p]
                self._free.append(p)


@dataclasses.dataclass
class _PrefixEntry:
    """One cached full page of prompt KV: the physical page plus its
    position in the hash chain (parent = digest of the preceding block,
    ``None`` at the root)."""
    page: int
    parent: Optional[str]
    children: Set[str] = dataclasses.field(default_factory=set)


class PrefixCache:
    """Content-addressed cache of **full, immutable** prompt-prefix pages.

    Keys are chain digests over page-aligned token blocks:
    ``d_j = H(d_{j-1} || prompt[j*ps:(j+1)*ps])`` — so a digest commits to
    the *entire* prefix through block ``j``, and two prompts share page
    ``j`` iff their first ``(j+1)*ps`` tokens are identical.  KV contents
    are a pure function of those tokens and the weights (never of the
    per-slot watermark key/strength rows), which is exactly why sharing
    is sound across tenants.

    Only blocks fully covered by ``prompt[:S0-1]`` are share-eligible
    (``(S0 - 1) // page_size`` of them): the last prompt token always
    prefills privately so finalize has last-position logits to sample
    from, and decode appends land at ``pos >= S0`` — never inside a
    shared page.  The cache holds its own allocator reference per entry
    (entries survive the inserting slot's flush); eviction pops LRU
    entries whose page refcount is 1 (cache-only) and cascades to their
    descendants — a slot always references a *contiguous* chain from the
    root, so refcounts are monotone non-increasing along a chain and an
    evictable parent implies evictable children."""

    def __init__(self, allocator: PageAllocator, page_size: int):
        self.allocator = allocator
        self.page_size = int(page_size)
        self._entries: "OrderedDict[str, _PrefixEntry]" = OrderedDict()
        self.hits = 0          # blocks served from cache, cumulative
        self.misses = 0        # share-eligible blocks prefilled privately
        self.evictions = 0     # entries evicted, cumulative
        self.pages_saved = 0   # pages an admission shared instead of
        #                        allocating + prefilling (bumped by the
        #                        scheduler at admit time, not on lookups)

    # -- introspection -----------------------------------------------------

    @property
    def n_entries(self) -> int:
        return len(self._entries)

    @property
    def pages_held(self) -> int:
        """Pages the cache currently references (one per entry)."""
        return len(self._entries)

    # -- keying ------------------------------------------------------------

    @staticmethod
    def block_digest(parent: Optional[str], block: np.ndarray) -> str:
        h = hashlib.sha256()
        h.update((parent or "").encode("ascii"))
        h.update(np.ascontiguousarray(block, np.int32).tobytes())
        return h.hexdigest()

    def shareable_blocks(self, prompt: np.ndarray) -> int:
        """Number of share-eligible full pages: those covered by
        ``prompt[:S0-1]`` (the uncached tail keeps >= 1 token)."""
        return (len(prompt) - 1) // self.page_size

    def _chain(self, prompt: np.ndarray) -> List[str]:
        digests, parent = [], None
        ps = self.page_size
        for j in range(self.shareable_blocks(prompt)):
            d = self.block_digest(parent, prompt[j * ps:(j + 1) * ps])
            digests.append(d)
            parent = d
        return digests

    # -- lookup / insert / evict -------------------------------------------

    def lookup(self, prompt: np.ndarray) -> tuple:
        """Longest cached prefix chain of the prompt's share-eligible
        blocks -> ``(digests, pages)``.  Hits refresh LRU recency
        (ancestors first, so a chain evicts leaf-before-root).  No
        references are taken — the caller ``share``s each page only once
        admission is certain."""
        digests: List[str] = []
        pages: List[int] = []
        for d in self._chain(prompt):
            e = self._entries.get(d)
            if e is None:
                break
            digests.append(d)
            pages.append(e.page)
        for d in digests:
            self._entries.move_to_end(d)
        self.hits += len(digests)
        self.misses += self.shareable_blocks(prompt) - len(digests)
        return digests, pages

    def insert_chain(self, prompt: np.ndarray, hit_digests: List[str],
                     slot_pages: Sequence[int]) -> int:
        """Register a finalized slot's freshly written full-prefix pages
        (the blocks *after* its admission-time hits).  The cache takes
        its own allocator reference per new entry, so the pages outlive
        the slot's flush.  A digest that raced in via another slot keeps
        the incumbent entry (identical content — same token chain, same
        weights); the caller's private page stays private.  Returns the
        number of entries inserted."""
        chain = self._chain(prompt)
        parent = hit_digests[-1] if hit_digests else None
        inserted = 0
        for j in range(len(hit_digests), len(chain)):
            d = chain[j]
            incumbent = self._entries.get(d)
            if incumbent is not None:
                self._entries.move_to_end(d)
                parent = d
                continue
            page = int(slot_pages[j])
            self.allocator.share(page)
            self._entries[d] = _PrefixEntry(page=page, parent=parent)
            if parent is not None and parent in self._entries:
                self._entries[parent].children.add(d)
            inserted += 1
            parent = d
        return inserted

    def evict(self, n_pages: int, protect: Set[str] = frozenset()) -> int:
        """Free >= ``n_pages`` pages if possible by evicting LRU entries
        whose page refcount is 1 (cache-only — pages still referenced by
        live slots are skipped) and are not in ``protect`` (the hit chain
        of the admission that triggered the eviction).  Evicting an entry
        cascades to its descendants (see class docstring).  Returns the
        number of pages actually returned to the free list."""
        freed = 0
        for d in list(self._entries):
            if freed >= n_pages:
                break
            e = self._entries.get(d)
            if e is None or d in protect:
                continue           # already cascaded away, or protected
            if self.allocator.refcount(e.page) > 1:
                continue           # a live slot still reads this page
            freed += self._evict_subtree(d)
        return freed

    def _evict_subtree(self, d: str) -> int:
        e = self._entries.pop(d)
        if e.parent is not None and e.parent in self._entries:
            self._entries[e.parent].children.discard(d)
        freed = 0
        for c in list(e.children):
            if c in self._entries:
                freed += self._evict_subtree(c)
        assert self.allocator.refcount(e.page) == 1, \
            f"evicting cached page {e.page} still referenced by a slot"
        self.allocator.free([e.page])
        self.evictions += 1
        return freed + 1

    def clear(self) -> int:
        """Drop every entry (all must be cache-only) and return the pages
        to the pool; returns the number of pages freed."""
        return self.evict(len(self._entries))


def _write_slot_fn(state: Dict[str, Any], sub: Dict[str, Any], b
                   ) -> Dict[str, Any]:
    """Scatter a batch-1 engine state into slot ``b`` of the live state.

    Model caches carry their batch dim at axis 1 (leading layer axis)
    except the per-sequence ``pos`` vector; every other engine field is
    batch-leading; the scalar ``step_idx`` is shared (and irrelevant under
    pseudorandom accept)."""
    out: Dict[str, Any] = {}
    for k, v in state.items():
        if k in ("t_cache", "d_cache"):
            c = {}
            for ck, cv in v.items():
                if ck == "pos":
                    c[ck] = cv.at[b].set(sub[k][ck][0])
                else:
                    c[ck] = cv.at[:, b].set(sub[k][ck][:, 0]
                                            .astype(cv.dtype))
            out[k] = c
        elif getattr(v, "ndim", 0) >= 1:
            out[k] = v.at[b].set(sub[k][0])
        else:
            out[k] = v        # shared scalar step state
    return out


class Scheduler:
    """Continuous batching: ``batch`` live slots fed from a FIFO queue,
    with admission/flush at the loop's sync points.

    ``max_tokens`` bounds any request's ``n_tokens`` (it sizes the output
    buffers); ``max_prompt_len`` bounds prompt lengths (it sizes the KV
    caches).  ``eos_id`` (optional) terminates any slot that emits it.
    Pass ``mesh`` to run the loop sharded exactly as ``generate(mesh=...)``
    does — admission scatters into the sharded state, flush slices only
    the finished slot's rows.

    Compilation note: dense-cache admission prefills the raw prompt, so
    each *distinct prompt length* compiles its own prefill (the decode
    loop itself is shared across all requests).  For length-diverse
    production traffic either left-pad prompts to a few bucket lengths
    **before submission** — padding must be part of the request itself
    (solo ``generate`` of the padded prompt is the bit-exactness
    reference); the scheduler never pads silently, because that would
    change the watermark context hashes of early tokens — or use the
    paged path (``page_size=``), whose chunked prefill admits every
    prompt through one fixed ``(prefill_chunk,)``-shaped ``extend_step``
    compile regardless of length (the chunk *padding* there is pure
    compute shape: padded tail positions are beyond ``pos``, never hashed
    into any context and never attended).

    Paged mode (``page_size=`` + ``num_pages=``): KV lives in a shared
    pool of fixed pages; a slot's footprint is the pages its committed
    prefix needs, grown at sync points (``PageAllocator``).  Admission
    runs chunked prefill, one chunk per slot per sync round, interleaved
    with decode.  Pool exhaustion while *growing a live slot* raises
    ``RuntimeError`` (mid-request eviction is not supported) — size
    ``num_pages`` for the worst-case concurrently-live footprint;
    admission itself simply waits for pages (head-of-line, FIFO kept).

    ``prefix_cache=True`` shares full prompt-prefix pages across
    requests (see the module docstring and ``PrefixCache``): admissions
    whose prompts repeat a cached page-aligned prefix skip its prefill
    entirely and reference the resident pages; flush drops references
    instead of freeing, and cold cache entries are evicted LRU when the
    pool runs short.

    **Streaming & overlap** (``docs/serving.md``): every sync round makes
    ONE batched host transfer (flags + the live slots' buffer rows) and
    surfaces newly committed tokens through ``on_token`` /
    ``run_stream()`` before flushing; per-request TTFT and inter-token
    gaps land in ``RequestResult`` and ``stats()``.  ``overlap=True``
    dispatches the next decode chunk *before* the round's host work and
    snapshots the chunk's input instead of its output: the device
    computes while the host streams/flushes/admits, at the cost of
    one-chunk token-visibility latency and a doubled paged page-growth
    horizon (size ``num_pages`` accordingly).  Served bits are identical
    either way — admission still lands only between chunks."""

    def __init__(self, t_params, d_params, tcfg: ModelConfig,
                 dcfg: ModelConfig, scfg: E.SpecConfig, *, batch: int,
                 key, max_tokens: int, max_prompt_len: int = 64,
                 eos_id: Optional[int] = None, sync_every: int = 8,
                 mesh=None, shard_params: bool = True,
                 page_size: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 prefix_cache: bool = False,
                 key_pool=None, strength_controller=None,
                 overlap: bool = False,
                 on_token: Optional[Callable[[int, int, dict], None]] = None,
                 on_result: Optional[Callable[[RequestResult], None]] = None):
        if scfg.accept != "pseudorandom":
            raise ValueError(
                "continuous batching requires accept='pseudorandom': "
                "'standard' coins draw from the global step index, which "
                "depends on the other slots' schedules and would break "
                "slot isolation")
        if tcfg.arch_type in ("audio", "vlm"):
            raise ValueError(
                f"continuous batching does not support arch_type="
                f"{tcfg.arch_type!r} yet: admission prefills text-only "
                "prompts and has no per-request modality extras "
                "(audio_emb/image_emb) — use generate(extras=...) with "
                "fixed batches")
        # numeric/combo validation is centralized on ServeConfig — the
        # scheduler folds its kwargs into one and validates; only the
        # model/drafter compatibility rules below stay inline (they need
        # the configs and the drafter registry, not just the knobs)
        ServeConfig(batch=batch, key=key, max_tokens=max_tokens,
                    max_prompt_len=max_prompt_len, eos_id=eos_id,
                    sync_every=sync_every, mesh=mesh,
                    shard_params=shard_params, page_size=page_size,
                    num_pages=num_pages, prefill_chunk=prefill_chunk,
                    prefix_cache=prefix_cache, key_pool=key_pool,
                    strength_controller=strength_controller,
                    overlap=overlap, on_token=on_token,
                    on_result=on_result).validate()
        self.tcfg, self.dcfg, self.scfg = tcfg, dcfg, scfg
        self.B = batch
        self._stat_scheme = E.make_decoder(scfg).name
        # default serving word: every request without a pool/explicit key
        # serves under the scheduler key — bit-identical to single-tenant
        self.key_word = int(np.asarray(jax.device_get(
            prf.as_key_word(key))))
        self.key_pool = key_pool
        self.strength_controller = strength_controller
        # per-slot serving metadata (host mirrors of the state rows)
        self._slot_key: List[int] = [self.key_word] * batch
        self._slot_strength: List[float] = [1.0] * batch
        self._slot_tier: List[Optional[str]] = [None] * batch
        self._slot_pooled: List[bool] = [False] * batch
        self.max_tokens = max_tokens
        self.max_prompt_len = max_prompt_len
        self.eos_id = eos_id
        self.sync_every = sync_every
        self.mesh = mesh
        self.overlap = bool(overlap)
        self.on_token = on_token
        self.on_result = on_result
        K1 = scfg.K + 1
        self.max_seq = max_prompt_len + 1 + K1 * max_tokens + 2
        self.cap = max_tokens + K1 + 1
        # streaming/timing state: tokens already surfaced per slot, host
        # mirrors of the last snapshot's pos/done (what _ensure_pages
        # plans from — no extra device polls), submit times and per-token
        # visibility times keyed by uid
        self._streamed = np.zeros((batch,), np.int64)
        self._pos_host = np.zeros((batch,), np.int64)
        self._done_host = np.zeros((batch,), bool)
        self._t_submit: Dict[int, float] = {}
        self._arrivals: Dict[int, List[float]] = {}
        self.n_rounds = 0
        # live counters (``stats()``), kept from each round's snapshot:
        # sync rounds and loop steps run, and the alive slot-steps and
        # accepted draft tokens the slots gained since the last snapshot
        # (host mirrors of the per-slot device counters, zeroed where a
        # slot is armed, as the device rows are)
        self._rounds = self._loop_steps = 0
        self._live_slot_steps = self._accepted_live = 0
        self._alive_host = np.zeros((batch,), np.int64)
        self._acc_host = np.zeros((batch,), np.int64)

        self._drafter = get_drafter(dcfg)
        self.paged = page_size is not None
        if self.paged:
            if tcfg.arch_type in ("ssm", "hybrid"):
                raise ValueError(
                    f"paged KV caching needs an attention *target*; "
                    f"target arch_type={tcfg.arch_type!r} keeps O(1) "
                    "recurrent state per slot (nothing to page).  "
                    "Recurrent models are served as *drafters* — their "
                    "state lives dense outside the pool (serve.drafter)")
            if not self._drafter.chunked_extend:
                raise ValueError(
                    f"paged admission needs chunked prefill, which the "
                    f"{self._drafter.name!r} drafter (draft arch_type="
                    f"{dcfg.arch_type!r}) does not support — its "
                    "shared-attention sites have no chunked extend.  "
                    "Serve hybrid drafters through the dense path "
                    "(page_size=None)")
            if prefix_cache and not self._drafter.paged_cache:
                raise ValueError(
                    "prefix_cache=True shares resident prompt KV pages, "
                    "but a recurrent drafter's dense state must be "
                    "recomputed over the *full* prompt — prefix hits "
                    "would skip draft-state prefill.  Serve recurrent "
                    "drafters with prefix_cache=False")
            self.page_size = int(page_size)
            self.num_pages = int(num_pages)
            self.prefill_chunk = int(prefill_chunk) if prefill_chunk else 8
            # logical extent of one slot's table — covers every position a
            # slot can *read* (reads stop at pos <= max_seq; write overruns
            # beyond the table clamp to the null page)
            self.max_pages = -(-self.max_seq // self.page_size)
            self._alloc = PageAllocator(self.num_pages)
            self._slot_pages: List[List[int]] = [[] for _ in range(batch)]
            self._chunk_cursor = np.zeros((batch,), np.int64)
            self._total_chunks = 0                  # deadlock bound term
            self._prefix = (PrefixCache(self._alloc, self.page_size)
                            if prefix_cache else None)
            # tokens already resident via shared pages at admission: the
            # chunked prefill of slot b starts at this offset
            self._prefill_base = np.zeros((batch,), np.int64)
            self._slot_hit_digests: List[List[str]] = \
                [[] for _ in range(batch)]

        self.queue: Deque[Request] = deque()
        self.slots = [_Slot() for _ in range(batch)]
        self.n_tok = np.zeros((batch,), np.int32)   # per-slot targets
        # observability: uids in admission order — the FIFO-fairness
        # witness asserted by the tests (result ordering itself is by uid)
        self.admit_order: List[int] = []
        # paged-mode event log: ("admit_chunk", uid, i) / ("finalize", uid)
        # / ("flush", uid) / ("admit_shared", uid, n_cached_tokens) in
        # wall order — the no-stall interleaving witness (short requests
        # flush *between* a long prompt's chunks) and the prefix-hit
        # witness asserted by the cache-parity tests
        self.events: List[tuple] = []
        self.results: Dict[int, RequestResult] = {}
        self._next_uid = 0
        self._total_target = 0                      # deadlock bound
        # cumulative honest serving stats (alive slot-steps only)
        self._acc = self._emitted = self._alive = 0

        if self.paged:
            # zeroed paged state: all-null page tables, pos 0 — slots fill
            # in place via chunked prefill + the jitted finalize
            # the KV pools take the target params' dtype (bf16 weights
            # -> bf16 pools), as the dense path's prefill caches do
            state = E.init_empty_paged_state(
                tcfg, dcfg, scfg, batch, num_pages=self.num_pages,
                page_size=self.page_size, max_pages=self.max_pages,
                max_seq=self.max_seq, cache_dtype=t_params["embed"].dtype)
        else:
            # a dummy prefill gives the state its shapes; every slot
            # starts FREE (done-masked), overwritten by its first admission
            dummy = jnp.zeros((batch, min(8, max_prompt_len)), jnp.int32)
            state = E.init_state(t_params, d_params, tcfg, dcfg, scfg,
                                 dummy, self.max_seq, key)
        self.carry = E.init_gen_carry(state, np.ones((batch,), np.int32),
                                      self.cap, eos_id)
        self._eos = jnp.int32(-1 if eos_id is None else eos_id)

        if mesh is not None:
            t_sh = (E.SHR.param_shardings(E._abs_tree(t_params), mesh)
                    if shard_params
                    else E.replicated_shardings(t_params, mesh))
            d_sh = (E.SHR.param_shardings(E._abs_tree(d_params), mesh)
                    if shard_params
                    else E.replicated_shardings(d_params, mesh))
            self._loop = E._jitted_gen_loop(
                tcfg, dcfg, scfg, mesh, carry_abs=E._abs_tree(self.carry),
                t_shardings=t_sh, d_shardings=d_sh)
            self.t_params = jax.device_put(t_params, t_sh)
            self.d_params = jax.device_put(d_params, d_sh)
            self.carry = jax.device_put(
                self.carry, E.carry_shardings(E._abs_tree(self.carry),
                                              mesh))
        else:
            self._loop = E._jitted_gen_loop(tcfg, dcfg, scfg)
            self.t_params, self.d_params = t_params, d_params
        self._admit_jit = jax.jit(self._admit_fn)
        # one (traced-slot) row gather shared by every snapshot: compiles
        # once, so per-round transfers never trigger per-length slice
        # compiles the way the old `carry["toks"][b, :n]` fetches did
        self._row_jit = jax.jit(self._row_fn)
        if self.paged:
            # each compiles exactly once: fixed (prefill_chunk,) /
            # (max_pages,) shapes regardless of prompt length
            self._chunk_jit = jax.jit(self._chunk_fn)
            self._finalize_jit = jax.jit(self._finalize_fn)
            self._set_table_jit = jax.jit(self._set_table_fn)

    # -- request intake ----------------------------------------------------

    def submit(self, prompt, n_tokens: int, uid: Optional[int] = None,
               key=None, tier: Optional[str] = None) -> int:
        """Queue one prompt; returns its uid (FIFO admission order).
        ``key``/``tier`` carry the request's watermark key word and
        strength class to admission (``_resolve_key``)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if not 1 <= len(prompt) <= self.max_prompt_len:
            raise ValueError(f"prompt length {len(prompt)} outside "
                             f"[1, {self.max_prompt_len}]")
        if not 1 <= n_tokens <= self.max_tokens:
            raise ValueError(f"n_tokens={n_tokens} outside "
                             f"[1, {self.max_tokens}]")
        if uid is None:
            uid = self._next_uid
        elif (uid in self.results
              or any(r.uid == uid for r in self.queue)
              or any(s.request is not None and s.request.uid == uid
                     for s in self.slots)):
            raise ValueError(f"uid {uid} already queued, active or served "
                             "— a duplicate would overwrite its result")
        self._next_uid = max(self._next_uid, uid) + 1
        self.queue.append(Request(prompt=prompt, n_tokens=int(n_tokens),
                                  uid=uid, key=key, tier=tier))
        self._t_submit[uid] = time.perf_counter()
        self._total_target += int(n_tokens)
        if self.paged:
            self._total_chunks += -(-len(prompt) // self.prefill_chunk)
        return uid

    def submit_many(self, requests: Sequence) -> List[int]:
        """Queue requests in order (see ``as_request`` for the accepted
        formats)."""
        return [self.submit(r.prompt, r.n_tokens,
                            uid=None if r.uid < 0 else r.uid,
                            key=r.key, tier=r.tier)
                for r in map(as_request, requests)]

    # -- admission (sync point) --------------------------------------------

    def _admit_fn(self, carry, sub, b, n_tok_b):
        """Jitted: scatter a batch-1 prefill into slot b of the carry —
        state rows, buffer slot 0 (the prefill sample + its metadata), and
        fresh per-slot flags/counters."""
        state = _write_slot_fn(carry["state"], sub, b)
        eos0 = sub["last"][0] == self._eos

        def row0(buf, v0):
            # v0 is the slot-0 value: a scalar, or a (stat_dim,) vector
            # for the widened detection-stat buffers
            row = jnp.zeros(buf.shape[1:], buf.dtype)
            return buf.at[b].set(row.at[0].set(v0.astype(buf.dtype)))

        zero = jnp.zeros((), jnp.int32)
        return dict(
            carry, state=state,
            toks=row0(carry["toks"], sub["last"][0]),
            fd=row0(carry["fd"], zero.astype(jnp.int8)),
            us=row0(carry["us"], sub["last_u"][0]),
            chs=row0(carry["chs"], sub["last_ctx"][0]),
            msk=row0(carry["msk"], sub["last_msk"][0]),
            yd=row0(carry["yd"], sub["last_yd"][0]),
            yt=row0(carry["yt"], sub["last_yt"][0]),
            lens=carry["lens"].at[b].set(1),
            eos=carry["eos"].at[b].set(eos0),
            done=carry["done"].at[b].set(eos0 | (n_tok_b <= 1)),
            total=carry["total"].at[b].set(0),
            acc_total=carry["acc_total"].at[b].set(0),
            alive_steps=carry["alive_steps"].at[b].set(0),
        )

    def _resolve_key(self, req: Request, b: int) -> None:
        """Assign the request's serving key word + strength gamma to slot
        ``b``: an explicit ``Request.key``, the pool's least-loaded active
        word, or the scheduler default; ``Request.tier`` goes through the
        strength controller.  Pool words are refcounted until flush.

        Ordering matters for error hygiene: the tier -> gamma resolution
        (which can raise on an unknown tier or a missing controller) runs
        *before* ``KeyPool.acquire`` takes a reference, so a failed
        resolution leaves the pool untouched.  Callers in turn resolve
        before allocating pages or mutating slot state — a raise here
        must leave the scheduler exactly as it was."""
        if req.tier is not None:
            if self.strength_controller is None:
                raise ValueError(
                    f"request uid={req.uid} names strength tier "
                    f"{req.tier!r} but the scheduler was built without a "
                    "strength_controller")
            gamma = float(self.strength_controller.pick(req.tier))
        else:
            gamma = 1.0
        pooled = False
        if self.key_pool is not None:
            word = self.key_pool.acquire(req.key)
            pooled = True
        elif req.key is not None:
            word = int(np.asarray(jax.device_get(
                prf.as_key_word(req.key))))
        else:
            word = self.key_word
        self._slot_key[b] = word
        self._slot_strength[b] = gamma
        self._slot_tier[b] = req.tier
        self._slot_pooled[b] = pooled

    def _arm(self, b: int) -> None:
        """Host side of arming slot ``b`` (``_admit_fn`` / ``_finalize_fn``
        zero its device flags and counters): it decodes from its first
        token, none streamed yet, and its live-counter mirrors restart."""
        self.slots[b].phase = DECODING
        self._streamed[b] = 0
        self._done_host[b] = False
        self._alive_host[b] = self._acc_host[b] = 0
        self.admit_order.append(self.slots[b].request.uid)

    def _admit(self) -> List[int]:
        """Fill every FREE slot from the queue head (FIFO); returns the
        admitted uids."""
        if self.paged:
            return self._admit_paged()
        uids = []
        for b, slot in enumerate(self.slots):
            if not self.queue:
                break
            if slot.phase != FREE:
                continue
            req = self.queue[0]
            # resolve key/tier BEFORE touching slot state: a resolution
            # failure (unknown tier, pool misuse) must leave the slot
            # FREE and the request queued, not strand it PREFILLING
            self._resolve_key(req, b)
            self.queue.popleft()
            slot.phase, slot.request = PREFILLING, req
            sub = E.init_state(self.t_params, self.d_params, self.tcfg,
                               self.dcfg, self.scfg, req.prompt[None],
                               self.max_seq, self._slot_key[b],
                               strength=self._slot_strength[b])
            self.carry = self._admit_jit(self.carry, sub, jnp.int32(b),
                                         jnp.int32(req.n_tokens))
            self.n_tok[b] = req.n_tokens
            self._arm(b)
            uids.append(req.uid)
        return uids

    # -- paged admission: page tables + chunked prefill --------------------

    def _table_row(self, b: int) -> jnp.ndarray:
        """Slot ``b``'s (max_pages,) page-table row: its allocated pages
        then null-page (0) padding."""
        row = np.zeros((self.max_pages,), np.int32)
        pages = self._slot_pages[b]
        row[:len(pages)] = pages
        return jnp.asarray(row)

    def _set_table_fn(self, carry, b, row):
        """Jitted: write one (max_pages,) table row into slot ``b`` of
        every cache that pages (one logical allocation serves both models
        — their ``pos`` advance in lockstep, so identical rows are
        correct).  A recurrent drafter's dense cache has no table: its
        state lives outside the pool and is skipped."""
        state = carry["state"]

        def upd(c):
            if "page_table" not in c:
                return c
            return dict(c, page_table=c["page_table"].at[b].set(row))

        state = dict(state, t_cache=upd(state["t_cache"]),
                     d_cache=upd(state["d_cache"]))
        return dict(carry, state=state)

    def _chunk_fn(self, t_params, d_params, carry, toks, b, start_pos,
                  new_pos):
        """Jitted (compiles once — fixed (prefill_chunk,) shape): run one
        prompt chunk through both models' paged ``extend_step`` for slot
        ``b`` and return (carry, target logits (1, ck, V)).

        The target extends its shared pool through slot ``b``'s table row
        (``drafter.paged_slot_extend`` — writes land only in that slot's
        pages; the padded tail sits beyond ``pos``, hidden by the
        position gate until decode overwrites it).  The draft side goes
        through the drafter: attention drafts page identically, recurrent
        drafts run the chunked-scan continuation on a batch-1 slice of
        their dense state rows (``models.ssm.extend_step``, padded tail
        identity-masked via ``n_valid``)."""
        state = carry["state"]
        with E.SHR.mesh_context(self.mesh):
            t_logits, t_cache = paged_slot_extend(
                t_params, self.tcfg, toks, state["t_cache"], b, start_pos,
                new_pos)
            _, d_cache = self._drafter.slot_extend(
                d_params, self.dcfg, toks, state["d_cache"], b, start_pos,
                new_pos)
        state = dict(state, t_cache=t_cache, d_cache=d_cache)
        return dict(carry, state=state), t_logits

    def _finalize_fn(self, carry, key_word, strength, logits, b, last_idx,
                     window_row, n_tok_b):
        """Jitted: sample the prefill token of slot ``b`` from its last
        prompt-position logits and arm the slot — the paged counterpart of
        ``_admit_fn``, sharing ``engine.first_token_meta`` with
        ``init_state`` so both admission paths are bit-identical.  The
        slot's key word and strength gamma land in the state's per-slot
        rows here (the paged analogue of ``init_state(key, strength)``)."""
        dec = E.make_decoder(self.scfg)
        state = carry["state"]
        last_logits = jax.lax.dynamic_index_in_dim(logits, last_idx,
                                                   axis=1, keepdims=False)
        meta = E.first_token_meta(dec, self.scfg, key_word, last_logits,
                                  window_row[None], self.tcfg.vocab,
                                  strength=strength)
        pos_b = jax.lax.dynamic_index_in_dim(state["t_cache"]["pos"], b,
                                             keepdims=False)
        hist_row = jnp.zeros((self.scfg.history_cap,), jnp.uint32)
        gated0 = meta["last_msk"][0]
        state = dict(
            state,
            keys=state["keys"].at[b].set(
                jnp.asarray(key_word).astype(jnp.uint32)),
            strength=state["strength"].at[b].set(
                jnp.asarray(strength).astype(jnp.float32)),
            window=state["window"].at[b].set(meta["window"][0]),
            last=state["last"].at[b].set(meta["last"][0]),
            last_ctx=state["last_ctx"].at[b].set(meta["last_ctx"][0]),
            last_u=state["last_u"].at[b].set(meta["last_u"][0]),
            last_msk=state["last_msk"].at[b].set(meta["last_msk"][0]),
            last_yd=state["last_yd"].at[b].set(meta["last_yd"][0]),
            last_yt=state["last_yt"].at[b].set(meta["last_yt"][0]),
            n_committed=state["n_committed"].at[b].set(pos_b + 1),
            hist=state["hist"].at[b].set(hist_row.at[0].set(
                jnp.where(gated0, jnp.uint32(0), meta["last_ctx"][0]))),
            hist_n=state["hist_n"].at[b].set((~gated0).astype(jnp.int32)),
        )
        eos0 = meta["last"][0] == self._eos

        def row0(buf, v0):
            row = jnp.zeros(buf.shape[1:], buf.dtype)
            return buf.at[b].set(row.at[0].set(v0.astype(buf.dtype)))

        zero = jnp.zeros((), jnp.int32)
        return dict(
            carry, state=state,
            toks=row0(carry["toks"], meta["last"][0]),
            fd=row0(carry["fd"], zero.astype(jnp.int8)),
            us=row0(carry["us"], meta["last_u"][0]),
            chs=row0(carry["chs"], meta["last_ctx"][0]),
            msk=row0(carry["msk"], meta["last_msk"][0]),
            yd=row0(carry["yd"], meta["last_yd"][0]),
            yt=row0(carry["yt"], meta["last_yt"][0]),
            lens=carry["lens"].at[b].set(1),
            eos=carry["eos"].at[b].set(eos0),
            done=carry["done"].at[b].set(eos0 | (n_tok_b <= 1)),
            total=carry["total"].at[b].set(0),
            acc_total=carry["acc_total"].at[b].set(0),
            alive_steps=carry["alive_steps"].at[b].set(0),
        )

    def _admit_paged(self) -> List[int]:
        """Reserve pages + page tables for queued prompts (FIFO with
        head-of-line blocking on pool space — never reorders) and mark
        their slots PREFILLING; the actual prompt tokens stream in via
        ``_prefill_step``, one chunk per sync round.

        With a prefix cache, admission first looks up the prompt's
        full-page prefix chain: every hit page is ``share``d into the new
        slot's table (no prefill work), and only the uncached tail
        allocates private pages and chunk-prefills — starting at the
        cached-token offset (``_prefill_base``).  Under pool pressure the
        cache evicts LRU cache-only entries (the hit chain itself is
        protected) before admission gives up and waits head-of-line.

        Order of operations is the error-hygiene contract: lookup and
        eviction mutate nothing a failure could leak; ``_resolve_key``
        (which can raise) runs before any page is allocated or any slot
        state is touched; the share/alloc that follow cannot fail (free
        space was just checked and the scheduler is single-threaded)."""
        uids = []
        for b, slot in enumerate(self.slots):
            if not self.queue:
                break
            if slot.phase != FREE:
                continue
            req = self.queue[0]
            total = -(-len(req.prompt) // self.page_size)
            if self._prefix is not None:
                digests, shared = self._prefix.lookup(req.prompt)
            else:
                digests, shared = [], []
            need = total - len(shared)
            if need > self._alloc.n_free and self._prefix is not None:
                self._prefix.evict(need - self._alloc.n_free,
                                   protect=set(digests))
            if need > self._alloc.n_free:
                break
            self._resolve_key(req, b)      # may raise: nothing held yet
            self.queue.popleft()
            for p in shared:
                self._alloc.share(p)
            self._slot_pages[b] = list(shared) + self._alloc.alloc(need)
            self._slot_hit_digests[b] = list(digests)
            self._prefill_base[b] = len(shared) * self.page_size
            self.carry = self._set_table_jit(self.carry, jnp.int32(b),
                                             self._table_row(b))
            slot.phase, slot.request = PREFILLING, req
            self._chunk_cursor[b] = 0
            if shared:
                self._prefix.pages_saved += len(shared)
                self.events.append(
                    ("admit_shared", req.uid, int(self._prefill_base[b])))
            uids.append(req.uid)
        return uids

    def _prefill_step(self) -> None:
        """Advance every PREFILLING slot by ONE prompt chunk (so a long
        prompt yields to the decode loop between chunks); the slot's last
        chunk also runs the finalize (first-token sample) and flips it to
        DECODING."""
        for b, slot in enumerate(self.slots):
            if slot.phase == PREFILLING:
                with TraceAnnotation("serve.prefill", uid=slot.request.uid,
                                     chunk=int(self._chunk_cursor[b])):
                    self._prefill_chunk(b)

    def _prefill_chunk(self, b: int) -> None:
        """Slot ``b``'s next prompt chunk, and its finalize after the last."""
        req = self.slots[b].request
        S0, ck = len(req.prompt), self.prefill_chunk
        i = int(self._chunk_cursor[b])
        # prefix-cache hits are already resident: chunk i covers
        # prompt[base + i*ck : base + (i+1)*ck] (base is 0 without a cache;
        # the share-eligibility rule keeps base <= S0 - 1, so every slot
        # prefills >= 1 token and finalize always has its last-position
        # logits)
        start = int(self._prefill_base[b]) + i * ck
        chunk = np.zeros((ck,), np.int32)
        chunk[:min(ck, S0 - start)] = req.prompt[start:start + ck]
        new_pos = min(start + ck, S0)
        self.carry, logits = self._chunk_jit(
            self.t_params, self.d_params, self.carry,
            jnp.asarray(chunk), jnp.int32(b), jnp.int32(start),
            jnp.int32(new_pos))
        self.events.append(("admit_chunk", req.uid, i))
        self._chunk_cursor[b] = i + 1
        if new_pos < S0:
            return
        c = self.scfg.ctx_window
        window = np.zeros((c,), np.int32)
        window[max(c - S0, 0):] = req.prompt[-c:]
        self.carry = self._finalize_jit(
            self.carry, jnp.uint32(self._slot_key[b]),
            jnp.float32(self._slot_strength[b]), logits, jnp.int32(b),
            jnp.int32(S0 - 1 - start), jnp.asarray(window),
            jnp.int32(req.n_tokens))
        self.n_tok[b] = req.n_tokens
        self._arm(b)
        # finalize leaves pos at the host-known S0: _ensure_pages can
        # plan the slot's first decode chunk without a device poll
        self._pos_host[b] = S0
        self.events.append(("finalize", req.uid))
        if self._prefix is not None:
            # every share-eligible block is now fully written: hand the new
            # full-prefix pages to the cache (it takes its own refs, so
            # they survive this slot's flush)
            self._prefix.insert_chain(req.prompt, self._slot_hit_digests[b],
                                      self._slot_pages[b])

    def _ensure_pages(self) -> None:
        """Grow every live DECODING slot's page run to cover the next
        decode chunk's write horizon (pos can advance ``sync_every *
        (K+1)`` and each step writes ``K`` ahead).  Mid-request pool
        exhaustion is fatal by design — no eviction — so it raises.

        ``pos``/``done`` come from the host mirrors of the last sync
        round's snapshot (or the host-known ``S0`` for a slot finalized
        this round) — no extra device polls.  Under ``overlap`` the
        snapshot lags one in-flight chunk, so the horizon must cover TWO
        chunks of advance; done-in-flight slots may grow a page or two
        spuriously, which the flush frees one round later."""
        if not any(s.phase == DECODING for s in self.slots):
            return
        pos, done = self._pos_host, self._done_host
        K1 = self.scfg.K + 1
        chunks_ahead = 2 if self.overlap else 1
        for b, slot in enumerate(self.slots):
            if slot.phase != DECODING or bool(done[b]):
                continue
            horizon = int(pos[b]) + (chunks_ahead * self.sync_every + 1) * K1
            need = min(-(-horizon // self.page_size), self.max_pages)
            grow = need - len(self._slot_pages[b])
            if grow <= 0:
                continue
            if grow > self._alloc.n_free and self._prefix is not None:
                # cache-only pages are reclaimable mid-flight: growing a
                # live slot outranks keeping cold prefixes warm
                self._prefix.evict(grow - self._alloc.n_free)
            try:
                self._slot_pages[b].extend(self._alloc.alloc(grow))
            except RuntimeError as e:
                raise RuntimeError(
                    f"KV page pool exhausted growing live slot {b} "
                    f"(uid={slot.request.uid}, pos={int(pos[b])}): {e}. "
                    "Mid-request eviction is unsupported — raise "
                    "num_pages to cover the worst-case live footprint."
                ) from e
            self.carry = self._set_table_jit(self.carry, jnp.int32(b),
                                             self._table_row(b))

    # -- decode chunk ------------------------------------------------------

    def _run_chunk(self):
        """Advance the jitted loop by up to ``sync_every`` steps (it exits
        earlier when every live slot drains).  The step limit is computed
        on device (``n_steps + sync_every``) so dispatching a chunk never
        blocks on the previous chunk's host sync — the enabler for
        ``overlap`` mode, and one less device round-trip without it."""
        n_tok = jnp.asarray(self.n_tok)
        limit = (self.carry["n_steps"] + self.sync_every).astype(jnp.int32)
        if self.mesh is not None:
            rep = NamedSharding(self.mesh, P())
            n_tok = jax.device_put(n_tok, rep)
        self.carry = self._loop(self.t_params, self.d_params, self.carry,
                                n_tok, self._eos, limit)

    # -- sync-point snapshot (one batched transfer per round) --------------

    _ROW_KEYS = ("toks", "fd", "us", "chs", "msk", "yd", "yt")
    _FLAG_KEYS = ("done", "eos", "lens", "total", "acc_total",
                  "alive_steps", "n_steps")

    def _row_fn(self, carry, b):
        """Jitted (compiles once — ``b`` is traced): slot ``b``'s full
        output/detection buffer rows.  Full-width rows, not ``[:lens]``
        slices: the host trims with the ``lens`` that arrives in the same
        batched transfer, and a fixed shape avoids one XLA slice compile
        per distinct committed length."""
        return {k: jax.lax.dynamic_index_in_dim(carry[k], b, axis=0,
                                                keepdims=False)
                for k in self._ROW_KEYS}

    def _snap_handles(self, carry) -> Dict[str, Any]:
        """Device handles for one sync round's host view: the (B,) flag
        vectors (+ paged ``pos``) and the full buffer rows of every
        DECODING slot — live rows only, never a full-buffer gather.
        Dispatch-only (no transfer): under ``overlap`` these gathers are
        enqueued *before* the next chunk, so fetching them never waits on
        the in-flight loop."""
        flags = {k: carry[k] for k in self._FLAG_KEYS}
        if self.paged:
            flags["pos"] = carry["state"]["t_cache"]["pos"]
        rows = {b: self._row_jit(carry, jnp.int32(b))
                for b, s in enumerate(self.slots) if s.phase == DECODING}
        return {"flags": flags, "rows": rows}

    def _take_snapshot(self, handles) -> Dict[str, Any]:
        """The round's ONE batched host transfer, plus host-mirror
        maintenance (``pos``/``done`` for ``_ensure_pages``, the live
        counters of ``stats()``)."""
        snap = jax.device_get(handles)
        flags = snap["flags"]
        if self.paged:
            self._pos_host[:] = np.asarray(flags["pos"])
        self._done_host[:] = np.asarray(flags["done"])
        alive = np.asarray(flags["alive_steps"], np.int64)
        acc = np.asarray(flags["acc_total"], np.int64)
        self._live_slot_steps += int((alive - self._alive_host).sum())
        self._accepted_live += int((acc - self._acc_host).sum())
        self._alive_host[:] = alive
        self._acc_host[:] = acc
        self._loop_steps = int(flags["n_steps"])
        return snap

    def _stream_events(self, snap, t_now: float
                       ) -> Iterator[Tuple[int, int, dict]]:
        """Surface every token the snapshot newly committed, in slot
        order: record its visibility time, fire ``on_token``, and yield
        ``(uid, token, meta)``.  Runs before ``_flush`` on the same
        snapshot, so a request's last token streams before its
        ``RequestResult`` exists."""
        flags = snap["flags"]
        for b, slot in enumerate(self.slots):
            if slot.phase != DECODING or b not in snap["rows"]:
                continue
            n = int(flags["lens"][b])
            start = int(self._streamed[b])
            if n <= start:
                continue
            uid = slot.request.uid
            toks = snap["rows"][b]["toks"]
            done = bool(flags["done"][b])
            t_rel = t_now - self._t_submit[uid]
            arr = self._arrivals.setdefault(uid, [])
            for i in range(start, n):
                arr.append(t_rel)
                meta = {"index": i, "round": self.n_rounds,
                        "t_rel_s": t_rel,
                        "final": done and i == n - 1}
                if self.on_token is not None:
                    self.on_token(uid, int(toks[i]), meta)
                yield (uid, int(toks[i]), meta)
            self._streamed[b] = n

    # -- flush (sync point) ------------------------------------------------

    def _flush(self, snap) -> List[RequestResult]:
        """Collect every DECODING slot whose ``done`` flag is set in the
        round's snapshot: trim its already-fetched rows, build the
        RequestResult, free the slot.  No device transfers — everything
        arrived in the snapshot's one batched get.  Under ``overlap`` the
        snapshot is the in-flight chunk's *input*, so a slot finishing
        inside that chunk flushes one round later (its snapshot rows are
        final: the loop freezes done slots and admissions never touch
        another slot's rows)."""
        flags = snap["flags"]
        out: List[RequestResult] = []
        for b, slot in enumerate(self.slots):
            if slot.phase != DECODING or not bool(flags["done"][b]):
                continue
            slot.phase = DRAINED
            n = int(flags["lens"][b])
            row = {k: np.asarray(v[:n])
                   for k, v in snap["rows"][b].items()}
            req = slot.request
            arrivals = np.asarray(self._arrivals.pop(req.uid, []),
                                  np.float64)
            self._t_submit.pop(req.uid, None)
            res = RequestResult(
                uid=req.uid, tokens=row["toks"],
                src=row["fd"], u=row["us"],
                ctx_hashes=row["chs"],
                masked=row["msk"], length=n,
                eos=bool(flags["eos"][b]),
                ttft_s=float(arrivals[0]) if len(arrivals) else None,
                arrivals_s=arrivals if len(arrivals) else None,
                alive_steps=int(flags["alive_steps"][b]),
                n_accepted=int(flags["acc_total"][b]),
                n_emitted=int(flags["total"][b]),
                y_draft=np.asarray(row["yd"]),
                y_target=np.asarray(row["yt"]),
                stat_scheme=self._stat_scheme,
                key_word=self._slot_key[b],
                strength=self._slot_strength[b],
                tier=self._slot_tier[b])
            self._acc += res.n_accepted
            self._emitted += res.n_emitted
            self._alive += res.alive_steps
            self.results[req.uid] = res
            out.append(res)
            slot.phase, slot.request = FREE, None
            self.n_tok[b] = 0
            self._streamed[b] = 0
            self._pos_host[b] = 0
            self._done_host[b] = False
            if self.on_result is not None:
                self.on_result(res)
            if self._slot_pooled[b]:
                self.key_pool.release(self._slot_key[b])
                self._slot_pooled[b] = False
            self._slot_key[b] = self.key_word
            self._slot_strength[b] = 1.0
            self._slot_tier[b] = None
            if self.paged:
                # drop the slot's page references AND null out its device
                # table: the freed slot keeps riding the loop done-masked,
                # and its frozen writes must land in the null page —
                # through the stale table they would corrupt reallocated
                # pages.  ``free`` decrements: private pages return to the
                # pool, prefix-shared pages survive under the cache's (or
                # another slot's) remaining references
                self._alloc.free(self._slot_pages[b])
                self._slot_pages[b] = []
                self._slot_hit_digests[b] = []
                self._prefill_base[b] = 0
                self.carry = self._set_table_jit(
                    self.carry, jnp.int32(b),
                    jnp.zeros((self.max_pages,), jnp.int32))
                self.events.append(("flush", req.uid))
        return out

    # -- drive -------------------------------------------------------------

    def _active(self) -> bool:
        return any(s.phase != FREE for s in self.slots)

    def run_stream(self) -> Iterator[Tuple[int, int, dict]]:
        """Drain the queue, yielding ``(uid, token, meta)`` as tokens
        surface at sync points; results land in ``self.results`` as slots
        flush (``meta``: token ``index`` in the request's stream, sync
        ``round``, visibility time ``t_rel_s`` relative to submit, and
        ``final`` on a request's last token).

        Per round: dispatch the next decode chunk, then do ALL host work
        — one batched transfer, streaming, flush, admission — and only
        then return to (maybe) wait on the device.  With ``overlap=True``
        the transfer snapshots the chunk's *input* (already materialized:
        it carries every admission/prefill op dispatched before the
        chunk), so host work runs concurrently with the in-flight chunk
        and a token becomes visible at most one chunk after it commits;
        with ``overlap=False`` it snapshots the chunk's output — exactly
        the strict sequential semantics, same code path.  Admission
        scatters always land between chunks (program order on the device
        queue), which is why overlap changes wall-clock packing but not a
        single served bit."""
        # every round either flushes a request, admits a prompt chunk, or
        # advances >= 1 committed token on some live slot, so this bound
        # is unreachable unless the scheduler genuinely deadlocks; under
        # overlap each flush wave trails the chunk that finished it by
        # one round, hence the extra len(queue) headroom
        limit = 4 + 2 * len(self.queue) + self._total_target
        if self.overlap:
            limit += 1 + len(self.queue)
        if self.paged:
            limit += self._total_chunks
        rounds = 0
        with TraceAnnotation("serve.admit") as span:
            self._set_uids(span, self._admit())
        self._check_paged_deadlock()
        while self.queue or self._active():
            rounds += 1
            self.n_rounds = rounds
            if rounds > limit:
                raise RuntimeError(
                    f"scheduler stalled after {rounds} sync rounds "
                    f"(queue={len(self.queue)}, "
                    f"slots={[s.phase for s in self.slots]})")
            self._rounds += 1
            with TraceAnnotation("serve.round", round=self._rounds):
                yield from self._round()

    def _round(self) -> Iterator[Tuple[int, int, dict]]:
        """One sync round of ``run_stream``, each phase in its own
        profiler span (recorded only while a trace is active)."""
        if self.paged:
            self._prefill_step()
            with TraceAnnotation("serve.pages"):
                self._ensure_pages()
        if self.overlap:
            # gathers enqueue BEFORE the chunk: device executes them
            # first, so the transfer below never waits on the chunk
            with TraceAnnotation("serve.snapshot"):
                handles = self._snap_handles(self.carry)
        with TraceAnnotation("serve.dispatch"):
            self._run_chunk()
        with TraceAnnotation("serve.snapshot"):
            if not self.overlap:
                handles = self._snap_handles(self.carry)
            snap = self._take_snapshot(handles)
        with TraceAnnotation("serve.stream"):
            yield from self._stream_events(snap, time.perf_counter())
        with TraceAnnotation("serve.flush") as span:
            self._set_uids(span, [r.uid for r in self._flush(snap)])
        with TraceAnnotation("serve.admit") as span:
            self._set_uids(span, self._admit())
        self._check_paged_deadlock()

    @staticmethod
    def _set_uids(span, uids: List[int]) -> None:
        """Tag a flush/admit span with the requests it served (space-
        separated: the profiler splits span arguments at commas)."""
        if uids:
            span.set_metadata(uids=" ".join(map(str, uids)))

    def run(self) -> List[RequestResult]:
        """Drain the queue: admit → decode chunk → flush, until every
        request completed.  Returns results in uid order.  (The streaming
        surface — ``on_token`` and per-request TTFT/gap timing — is live
        here too: ``run()`` just drains ``run_stream()``.)"""
        for _ in self.run_stream():
            pass
        return [self.results[uid] for uid in sorted(self.results)]

    def _check_paged_deadlock(self) -> None:
        """Every slot idle + a queue that admission skipped means the head
        prompt alone overflows the pool — waiting can never help.  (With a
        prefix cache, admission already evicted every reclaimable
        cache-only entry outside the head's own hit chain before giving
        up, so ``n_free`` here is post-eviction and the verdict final.)"""
        if not (self.paged and self.queue) or self._active():
            return
        req = self.queue[0]
        need = -(-len(req.prompt) // self.page_size)
        cached = ""
        if self._prefix is not None:
            _, shared = self._prefix.lookup(req.prompt)
            need -= len(shared)
            cached = (f" ({len(shared)} prefix pages cached, "
                      f"{self._prefix.pages_held} held by the cache)")
        raise RuntimeError(
            f"KV page pool too small: request uid={req.uid} needs {need} "
            f"pages for its {len(req.prompt)}-token prompt but only "
            f"{self._alloc.n_free} of {self.num_pages - 1} allocatable "
            f"pages exist (every slot idle){cached} — raise num_pages")

    def stats(self) -> Dict[str, float]:
        """Cumulative honest serving stats over flushed requests (drained
        slots never count toward the denominators), and live counters kept
        from every round's snapshot, which move while requests run:
        ``rounds`` (sync rounds), ``loop_steps`` (the loop's ``n_steps``),
        ``live_slot_steps`` and ``accepted_live`` (alive slot-steps and
        accepted draft tokens, over every request served or in flight)."""
        denom = max(self._alive, 1)
        out = {"served": float(len(self.results)),
               "aatps": self._acc / denom,
               "tokens_per_step": self._emitted / denom,
               "alive_slot_steps": float(self._alive),
               "rounds": float(self._rounds),
               "loop_steps": float(self._loop_steps),
               "live_slot_steps": float(self._live_slot_steps),
               "accepted_live": float(self._accepted_live)}
        ttfts = [r.ttft_s for r in self.results.values()
                 if r.ttft_s is not None]
        if ttfts:
            out["ttft_mean_s"] = float(np.mean(ttfts))
        gaps = [r.gaps_s for r in self.results.values()
                if r.gaps_s is not None]
        if gaps:
            allg = np.concatenate(gaps)
            out["gap_mean_s"] = float(allg.mean())
            out["gap_p95_s"] = float(np.percentile(allg, 95))
        if self.paged:
            out["pages_used"] = float(self._alloc.n_used)
            out["pages_free"] = float(self._alloc.n_free)
            out["pages_peak"] = float(self._alloc.n_used_peak)
            if self._prefix is not None:
                out["prefix_entries"] = float(self._prefix.n_entries)
                out["prefix_pages"] = float(self._prefix.pages_held)
                out["prefix_hits"] = float(self._prefix.hits)
                out["prefix_misses"] = float(self._prefix.misses)
                out["prefix_evictions"] = float(self._prefix.evictions)
                out["prefix_pages_saved"] = float(self._prefix.pages_saved)
        return out
