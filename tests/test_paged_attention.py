"""Block-paged KV path, bottom layers: the Pallas paged-decode kernel and
its jnp mirror are bit-identical to dense ``decode_attention`` over the
same logical entries; ``paged_cache_write`` routes writes through the page
indirection exactly like the dense write; the paged model branch
(``init_paged_cache`` + the ``extend_step`` page-table branch) reproduces
dense ``prefill`` logits bit-for-bit under chunked admission; and the
scheduler's ``PageAllocator`` upholds its no-double-allocation /
full-return invariants under interleaved admit/drain stress (hypothesis
property + an always-running numpy fallback + a nightly fragmentation
stress).
"""
import numpy as np
import pytest

from tests._hyp import HAVE_HYPOTHESIS, given, settings, st

V = 96


def _rand_paged(rng, *, B=3, n_pages=17, page_size=8, max_pages=4, Hkv=2,
                H=4, hd=16, Sq=3):
    """Random q + garbage-filled pools + a permuted page table (every
    slot's pages scattered over the pool, disjoint, never page 0)."""
    import jax.numpy as jnp
    q = jnp.asarray(rng.standard_normal((B, Sq, H, hd)), jnp.float32)
    k_pool = jnp.asarray(
        rng.standard_normal((n_pages, Hkv, page_size, hd)), jnp.float32)
    v_pool = jnp.asarray(
        rng.standard_normal((n_pages, Hkv, page_size, hd)), jnp.float32)
    perm = rng.permutation(np.arange(1, n_pages))[:B * max_pages]
    table = jnp.asarray(perm.reshape(B, max_pages).astype(np.int32))
    return q, k_pool, v_pool, table


def test_paged_mirror_matches_dense_gather():
    """The jnp mirror == dense decode_attention over the gathered cache,
    bit-for-bit, for scalar and per-slot divergent pos."""
    import jax.numpy as jnp
    from repro.kernels import ref
    from repro.models import layers as L
    rng = np.random.default_rng(0)
    q, k_pool, v_pool, table = _rand_paged(rng)
    k = ref.paged_gather(k_pool, table)
    v = ref.paged_gather(v_pool, table)
    for pos in (jnp.int32(7), jnp.asarray([5, 1, 20], jnp.int32)):
        dense = L.decode_attention(q, k, v, pos)
        paged = ref.paged_attention_ref(q, k_pool, v_pool, table, pos)
        np.testing.assert_array_equal(np.asarray(paged), np.asarray(dense))
        # the public layers entry point dispatches to the same math
        via_layers = L.paged_decode_attention(q, k_pool, v_pool, table, pos)
        np.testing.assert_array_equal(np.asarray(via_layers),
                                      np.asarray(dense))


def test_paged_kernel_interpret_matches_mirror():
    """The Pallas program (interpret mode off-TPU) is bit-identical to the
    mirror — the contract the TPU path is held to."""
    import jax.numpy as jnp
    from repro.kernels import ref
    from repro.kernels.paged_attention import paged_decode_attention
    rng = np.random.default_rng(1)
    q, k_pool, v_pool, table = _rand_paged(rng, Sq=4)
    for pos in (jnp.int32(9), jnp.asarray([3, 11, 27], jnp.int32)):
        mirror = ref.paged_attention_ref(q, k_pool, v_pool, table, pos)
        kern = paged_decode_attention(q, k_pool, v_pool, table, pos,
                                      interpret=True)
        np.testing.assert_array_equal(np.asarray(kern), np.asarray(mirror))


def test_paged_extent_invariance_and_null_page():
    """Masked lanes contribute exact zeros: growing the table with extra
    garbage pages — or pointing the tail at the null page — cannot change
    the output (the invariant that makes incremental page growth and
    freed-slot null writes safe)."""
    import jax.numpy as jnp
    from repro.kernels import ref
    rng = np.random.default_rng(2)
    q, k_pool, v_pool, table = _rand_paged(rng, max_pages=3)
    pos = jnp.asarray([5, 9, 2], jnp.int32)
    base = ref.paged_attention_ref(q, k_pool, v_pool, table, pos)
    # tail pages -> null page (what admission starts from / flush resets to)
    nulled = table.at[:, -1].set(0)
    np.testing.assert_array_equal(
        np.asarray(ref.paged_attention_ref(q, k_pool, v_pool, nulled, pos)),
        np.asarray(base))
    # wider table with extra live garbage pages (incremental growth)
    grown = jnp.concatenate(
        [table, jnp.asarray([[13], [14], [15]], jnp.int32)], axis=1)
    np.testing.assert_array_equal(
        np.asarray(ref.paged_attention_ref(q, k_pool, v_pool, grown, pos)),
        np.asarray(base))


def test_paged_cache_write_matches_dense():
    """paged_cache_write through a scattered table == dense cache_write on
    the gathered view, for scalar and divergent per-slot pos; overruns
    past the table land in the null page, real pages untouched."""
    import jax.numpy as jnp
    from repro.kernels import ref
    from repro.models import layers as L
    rng = np.random.default_rng(3)
    _, k_pool, _, table = _rand_paged(rng, B=2, max_pages=3)
    Sq, Hkv, hd = 4, 2, 16
    kv = jnp.asarray(rng.standard_normal((2, Sq, Hkv, hd)), jnp.float32)
    for pos in (jnp.int32(6), jnp.asarray([2, 13], jnp.int32)):
        got = ref.paged_gather(
            L.paged_cache_write(k_pool, table, kv, pos), table)
        want = L.cache_write(ref.paged_gather(k_pool, table), kv,
                             jnp.broadcast_to(jnp.atleast_1d(pos), (2,)))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # overrun: writes beyond the table extent go to page 0 only
    far = jnp.asarray([23, 23], jnp.int32)       # 24 > 3*8 after 1 token
    out = L.paged_cache_write(k_pool, table, kv, far)
    touched = np.flatnonzero(np.any(
        np.asarray(out) != np.asarray(k_pool), axis=(1, 2, 3)))
    allowed = set(np.asarray(table).ravel().tolist()) | {0}
    assert set(touched.tolist()) <= allowed


def test_paged_model_chunked_prefill_matches_dense():
    """Model level: a prompt admitted through the paged ``extend_step``
    branch in fixed chunks (padded tail included) produces the dense
    ``prefill`` logits at the last prompt position bit-exactly, and stays
    bit-exact through a subsequent extend + a pos-only rollback."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_smoke_config
    from repro.models import model as M
    from repro.models import transformer as T
    cfg = get_smoke_config("yi-6b", vocab=V, d_model=64, d_ff=128,
                           n_heads=2, n_kv_heads=2, head_dim=32)
    params = M.init_params(jax.random.key(0), cfg)
    rng = np.random.default_rng(4)
    S0, ck, ps = 7, 4, 4
    prompt = jnp.asarray(rng.integers(1, V, size=(1, S0)), jnp.int32)

    dense_logits, dense_cache = M.prefill(params, cfg, {"tokens": prompt},
                                          max_seq=32)
    cache = M.init_paged_cache(cfg, 1, num_pages=32, page_size=ps,
                               max_pages=8)
    cache = dict(cache, page_table=cache["page_table"]
                 .at[0, :4].set(jnp.asarray([3, 9, 5, 7], jnp.int32)))
    logits = None
    for i in range(-(-S0 // ck)):
        chunk = np.zeros((ck,), np.int32)
        chunk[:min(ck, S0 - i * ck)] = np.asarray(prompt[0])[i*ck:(i+1)*ck]
        logits, cache = T.extend_step(params, cfg, jnp.asarray(chunk)[None],
                                      cache)
        cache = dict(cache, pos=jnp.full((1,), min((i + 1) * ck, S0),
                                         jnp.int32))
    np.testing.assert_array_equal(
        np.asarray(logits[:, (S0 - 1) % ck]),
        np.asarray(dense_logits[:, -1]))

    # decode continuation stays bit-exact vs the dense cache path
    toks = jnp.asarray(rng.integers(1, V, size=(1, 3)), jnp.int32)
    dense_cache = dict(dense_cache, pos=jnp.full((1,), S0, jnp.int32))
    ld, dense_cache = T.extend_step(params, cfg, toks, dense_cache)
    lp, cache = T.extend_step(params, cfg, toks, cache)
    np.testing.assert_array_equal(np.asarray(lp), np.asarray(ld))
    # pos-only rollback (speculative rejection) — no page copies
    dense_cache = dict(dense_cache, pos=jnp.full((1,), S0 + 1, jnp.int32))
    cache = dict(cache, pos=jnp.full((1,), S0 + 1, jnp.int32))
    ld2, _ = T.extend_step(params, cfg, toks, dense_cache)
    lp2, _ = T.extend_step(params, cfg, toks, cache)
    np.testing.assert_array_equal(np.asarray(lp2), np.asarray(ld2))


def test_init_paged_cache_rejects_recurrent_and_cross():
    from repro.configs import get_smoke_config
    from repro.models import model as M
    with pytest.raises(ValueError, match="recurrent"):
        M.init_paged_cache(get_smoke_config("rwkv6-3b", vocab=V), 2,
                           num_pages=8, page_size=4, max_pages=4)
    with pytest.raises(ValueError):
        M.init_paged_cache(get_smoke_config("whisper-tiny", vocab=V), 2,
                           num_pages=8, page_size=4, max_pages=4)


# ---------------------------------------------------------------------------
# PageAllocator invariants
# ---------------------------------------------------------------------------


def _allocator_round_trip(num_pages, ops):
    """Drive an allocator through (kind, size) ops; check the invariants
    after every op.  ``ops``: list of alloc sizes; a negative value frees
    the oldest outstanding allocation."""
    from repro.serve.scheduler import PageAllocator
    alloc = PageAllocator(num_pages)
    held = []                                    # list of page lists
    for sz in ops:
        if sz < 0:
            if held:
                alloc.free(held.pop(0))
        else:
            try:
                pages = alloc.alloc(sz)
            except RuntimeError:
                assert sz > alloc.n_free         # only exhaustion raises
                continue
            assert len(pages) == sz
            held.append(pages)
        flat = [p for h in held for p in h]
        assert 0 not in flat                     # null page never issued
        assert len(flat) == len(set(flat))       # no double allocation
        assert alloc.n_used == len(flat)
        assert alloc.n_free == num_pages - 1 - len(flat)
    for h in held:
        alloc.free(h)
    # every page returned: the free list is whole again
    assert alloc.n_free == num_pages - 1 and alloc.n_used == 0
    assert sorted(alloc.alloc(num_pages - 1)) == list(range(1, num_pages))


def test_page_allocator_basic_and_errors():
    from repro.serve.scheduler import PageAllocator
    a = PageAllocator(8)
    got = a.alloc(3)
    assert len(got) == 3 and 0 not in got
    with pytest.raises(RuntimeError, match="exhausted"):
        a.alloc(5)                               # only 4 left
    with pytest.raises(ValueError):
        a.free([0])                              # null page is foreign
    a.free(got)
    with pytest.raises(ValueError):
        a.free([got[0]])                         # double free
    with pytest.raises(ValueError):
        PageAllocator(1)                         # nothing allocatable


def test_page_allocator_refcounts_share_and_peak():
    """Refcounted sharing: ``share`` bumps a held page, ``free``
    decrements, the page returns to the pool only at zero — and the
    share/free error surface (null page, free page, over-free) stays as
    loud as the non-shared one."""
    from repro.serve.scheduler import PageAllocator
    a = PageAllocator(8)
    p1, p2 = a.alloc(2)
    assert a.refcount(p1) == 1 and a.refcount(0) == 0
    assert a.share(p1) == 2 and a.share(p1) == 3
    a.free([p1]); a.free([p1])
    assert a.refcount(p1) == 1 and a.n_used == 2   # still held
    a.free([p1])
    assert a.refcount(p1) == 0 and a.n_used == 1   # now returned
    with pytest.raises(ValueError, match="double free"):
        a.free([p1])                               # over-free raises
    with pytest.raises(ValueError, match="sharing"):
        a.share(p1)                                # share of a free page
    with pytest.raises(ValueError, match="sharing"):
        a.share(0)                                 # null page never shared
    with pytest.raises(ValueError, match="sharing"):
        a.share(7 + 1)                             # foreign id
    # peak tracks distinct held pages, not references
    assert a.n_used_peak == 2
    a.share(p2)
    assert a.n_used_peak == 2
    a.alloc(3)
    assert a.n_used_peak == 4


def _refcount_round_trip(num_pages, ops):
    """Drive a refcounted allocator through ops, mirroring refcounts in a
    host-side model; ``ops``: >0 alloc(n) (one free-unit), 0 share the
    lowest held page (its own free-unit — the cache-eviction analogue),
    <0 free the oldest outstanding unit.  Invariants after every op:
    model == allocator refcounts (never negative — over-frees raise
    before corruption), null page never handed out, ``n_free + n_used ==
    num_pages - 1``, peak monotone."""
    from repro.serve.scheduler import PageAllocator
    alloc = PageAllocator(num_pages)
    refs = {}                                    # page -> expected count
    held = []                                    # list of free-units
    peak = 0
    for sz in ops:
        if sz < 0:
            if held:
                unit = held.pop(0)
                alloc.free(unit)
                for p in unit:
                    refs[p] -= 1
                    if refs[p] == 0:
                        del refs[p]
        elif sz == 0:
            if refs:
                p = min(refs)
                alloc.share(p)
                refs[p] += 1
                held.append([p])
        else:
            try:
                pages = alloc.alloc(sz)
            except RuntimeError:
                assert sz > alloc.n_free         # only exhaustion raises
                continue
            assert len(pages) == sz
            for p in pages:
                assert p not in refs             # no double allocation
                refs[p] = 1
            held.append(pages)
        peak = max(peak, len(refs))
        assert 0 not in refs                     # null page never issued
        assert all(c >= 1 for c in refs.values())
        assert {p: alloc.refcount(p) for p in refs} == refs
        assert alloc.n_used == len(refs)
        assert alloc.n_free + alloc.n_used == num_pages - 1
        assert alloc.n_used_peak == peak
    for unit in held:
        alloc.free(unit)
    assert alloc.n_free == num_pages - 1 and alloc.n_used == 0
    assert sorted(alloc.alloc(num_pages - 1)) == list(range(1, num_pages))


def test_page_allocator_refcount_numpy_stress():
    """Always-running randomized alloc/share/free interleaving (the
    hypothesis property below deepens this when the dev extra is
    installed)."""
    rng = np.random.default_rng(7)
    for trial in range(20):
        num_pages = int(rng.integers(2, 40))
        ops = [int(x) for x in rng.integers(-2, 6, size=60)]
        _refcount_round_trip(num_pages, ops)


@pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
@settings(max_examples=200, deadline=None)
@given(num_pages=st.integers(2, 64),
       ops=st.lists(st.integers(-2, 8), max_size=80))
def test_page_allocator_refcount_property(num_pages, ops):
    """Hypothesis: arbitrary interleavings of alloc/share/free/evict keep
    refcounts exact and non-negative, never hand out the null page, hold
    ``n_free + n_used == num_pages - 1``, and drain to a whole pool."""
    _refcount_round_trip(num_pages, ops)


def test_page_allocator_numpy_stress():
    """Always-running randomized admit/drain interleaving (the hypothesis
    property below deepens this when the dev extra is installed)."""
    rng = np.random.default_rng(5)
    for trial in range(20):
        num_pages = int(rng.integers(2, 40))
        ops = [int(x) for x in rng.integers(-1, 6, size=60)]
        _allocator_round_trip(num_pages, ops)


@pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
@settings(max_examples=200, deadline=None)
@given(num_pages=st.integers(2, 64),
       ops=st.lists(st.integers(-1, 8), max_size=80))
def test_page_allocator_property(num_pages, ops):
    """Hypothesis: for arbitrary interleaved alloc/free sequences the
    allocator never double-allocates, never issues the null page, raises
    exactly on exhaustion, and returns every page on drain."""
    _allocator_round_trip(num_pages, ops)


@pytest.mark.slow
def test_page_allocator_fragmentation_stress():
    """Nightly: long interleaved admit/drain churn with skewed sizes —
    after every full drain the pool reassembles completely (a free-list
    allocator cannot fragment, and this pins that no bookkeeping leaks
    under churn)."""
    rng = np.random.default_rng(6)
    for trial in range(200):
        num_pages = int(rng.integers(2, 257))
        sizes = rng.choice([1, 1, 2, 3, 5, 8, 13, 31], size=400)
        ops = [int(s) if rng.random() < 0.55 else -1 for s in sizes]
        _allocator_round_trip(num_pages, ops)


@pytest.mark.slow
def test_page_allocator_shared_prefix_fragmentation_stress():
    """Nightly: churn shaped like prefix-cache traffic — a few long-lived
    "prefix chains" each shared by many short-lived "requests" that also
    hold private tails, freed in arbitrary order.  Refcounts stay exact
    under deep sharing and the pool reassembles completely after every
    drain (plus a broadened random alloc/share/free sweep)."""
    from repro.serve.scheduler import PageAllocator
    rng = np.random.default_rng(8)
    for trial in range(60):
        num_pages = int(rng.integers(32, 257))
        alloc = PageAllocator(num_pages)
        chains = [alloc.alloc(int(rng.integers(1, 5)))
                  for _ in range(int(rng.integers(1, 4)))]
        requests = []
        for _ in range(300):
            if requests and (rng.random() < 0.45
                             or alloc.n_free < 8):
                shared, tail = requests.pop(int(rng.integers(
                    0, len(requests))))
                alloc.free(shared + tail)        # one decref per page
            elif alloc.n_free >= 8:
                chain = chains[int(rng.integers(0, len(chains)))]
                shared = chain[:int(rng.integers(0, len(chain) + 1))]
                for p in shared:
                    alloc.share(p)
                requests.append((list(shared),
                                 alloc.alloc(int(rng.integers(1, 5)))))
            # chain pages: 1 (own) + one per live sharer
            counts = {}
            for shared, _ in requests:
                for p in shared:
                    counts[p] = counts.get(p, 0) + 1
            for chain in chains:
                for p in chain:
                    assert alloc.refcount(p) == 1 + counts.get(p, 0)
            assert alloc.n_free + alloc.n_used == num_pages - 1
        for shared, tail in requests:
            alloc.free(shared + tail)
        for chain in chains:                     # cache-eviction analogue
            assert all(alloc.refcount(p) == 1 for p in chain)
            alloc.free(chain)
        assert alloc.n_used == 0 and alloc.n_free == num_pages - 1
        _refcount_round_trip(num_pages,
                             [int(x) for x in rng.integers(-2, 9, 300)])
