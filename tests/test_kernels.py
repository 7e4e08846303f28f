"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + property tests.
Kernels run in interpret mode on CPU (the exact program staged for TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, st

from repro.kernels import ops, ref

KEY = jax.random.key(99)


def _probs(seed, shape, dtype=jnp.float32, temp=1.0):
    p = jax.nn.softmax(
        jax.random.normal(jax.random.key(seed), shape) * temp, axis=-1)
    return p.astype(dtype)


def _seeds(seed, shape):
    return jax.random.bits(jax.random.key(seed), shape, dtype=jnp.uint32)


@pytest.mark.parametrize("B,V", [(1, 16), (4, 128), (5, 257), (2, 4096),
                                 (3, 50257)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gumbel_argmax_sweep(B, V, dtype):
    probs = _probs(B * V, (B, V), dtype)
    seeds = _seeds(B + V, (B,))
    tok_k, u_k = ops.gumbel_argmax(probs, seeds, interpret=True)
    tok_r, u_r = ref.gumbel_argmax_ref(probs.astype(jnp.float32), seeds)
    assert np.array_equal(np.asarray(tok_k), np.asarray(tok_r))
    np.testing.assert_allclose(np.asarray(u_k), np.asarray(u_r), rtol=1e-6)


@pytest.mark.parametrize("B,V,m", [(1, 16, 1), (4, 128, 8), (3, 1000, 30),
                                   (2, 4096, 4)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_tournament_sweep(B, V, m, dtype):
    probs = _probs(B + V + m, (B, V), dtype)
    seeds = _seeds(V + m, (B,))
    d_k = ops.tournament(probs, seeds, m=m, interpret=True)
    d_r = ref.tournament_ref(probs.astype(jnp.float32), seeds, m=m)
    np.testing.assert_allclose(np.asarray(d_k), np.asarray(d_r),
                               rtol=2e-3 if dtype == jnp.bfloat16 else 1e-5,
                               atol=1e-6)


@pytest.mark.parametrize("B,K,V", [(1, 1, 32), (4, 4, 128), (2, 3, 1000),
                                   (3, 5, 4097)])
def test_spec_verify_sweep(B, K, V):
    p = _probs(B * K, (B, K, V))
    q = _probs(B * K + 1, (B, K, V))
    toks = jax.random.randint(jax.random.key(B + K), (B, K), 0, V)
    u = jax.random.uniform(jax.random.key(K + V), (B, K))
    seeds = _seeds(B * K * V, (B, K))
    outs_k = ops.spec_verify(p, q, toks, u, seeds, interpret=True)
    outs_r = ref.spec_verify_ref(p, q, toks, u, seeds)
    for a, b, nm in zip(outs_k, outs_r, ["n_acc", "acc", "rtok", "ru"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   err_msg=nm)


def test_kernel_gumbel_is_unbiased():
    """The in-kernel PRF race is itself an unbiased sampler: over many
    seeds the argmax token frequency matches P."""
    V = 8
    P = _probs(7, (V,))
    n = 20000
    probs = jnp.broadcast_to(P, (n, V))
    seeds = jnp.arange(n, dtype=jnp.uint32)
    toks, _ = ops.gumbel_argmax(probs, seeds, block_rows=64, interpret=True)
    freq = np.bincount(np.asarray(toks), minlength=V) / n
    np.testing.assert_allclose(freq, np.asarray(P), atol=0.02)


def test_tournament_kernel_unbiased():
    V = 6
    P = _probs(8, (V,))
    n = 8000
    probs = jnp.broadcast_to(P, (n, V))
    seeds = jnp.arange(n, dtype=jnp.uint32)
    d = ops.tournament(probs, seeds, m=12, block_rows=64, interpret=True)
    np.testing.assert_allclose(np.asarray(d.mean(0)), np.asarray(P),
                               atol=0.02)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 6), st.integers(2, 300), st.integers(0, 2**31 - 1))
def test_gumbel_argmax_property(b, v, seed):
    probs = _probs(seed % 1013, (b, v))
    seeds = _seeds(seed % 509, (b,))
    tok_k, u_k = ops.gumbel_argmax(probs, seeds, interpret=True)
    tok_r, u_r = ref.gumbel_argmax_ref(probs, seeds)
    assert np.array_equal(np.asarray(tok_k), np.asarray(tok_r))
    assert np.all((np.asarray(u_k) > 0) & (np.asarray(u_k) < 1))


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 4), st.integers(1, 5), st.integers(2, 200),
       st.integers(0, 2**31 - 1))
def test_spec_verify_property(b, k, v, seed):
    p = _probs(seed % 881, (b, k, v))
    q = _probs(seed % 883, (b, k, v))
    toks = jax.random.randint(jax.random.key(seed % 887), (b, k), 0, v)
    u = jax.random.uniform(jax.random.key(seed % 907), (b, k))
    seeds = _seeds(seed % 911, (b, k))
    nk, ak, rk, _ = ops.spec_verify(p, q, toks, u, seeds, interpret=True)
    nr, ar, rr, _ = ref.spec_verify_ref(p, q, toks, u, seeds)
    assert np.array_equal(np.asarray(nk), np.asarray(nr))
    assert np.array_equal(np.asarray(ak), np.asarray(ar))
    assert np.array_equal(np.asarray(rk), np.asarray(rr))
    # invariants: 0 <= n_acc <= K; prefix structure
    assert np.all((np.asarray(nk) >= 0) & (np.asarray(nk) <= k))
    acc = np.asarray(ak)
    assert np.all(np.diff(acc, axis=1) <= 0)


# ---------------------------------------------------------------------------
# WKV kernel (RWKV6 recurrence, VMEM-resident state)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,S,H,hd,blk", [(1, 16, 2, 4, 8), (2, 37, 3, 8, 16),
                                          (3, 64, 1, 16, 32)])
def test_wkv_kernel_sweep(B, S, H, hd, blk):
    from repro.kernels.wkv import wkv_kernel, wkv_ref
    ks = jax.random.split(jax.random.key(B * S), 6)
    r = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, H, hd))
    v = jax.random.normal(ks[2], (B, S, H, hd))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, S, H, hd)))
    u = jax.random.normal(ks[4], (H, hd))
    s0 = jax.random.normal(ks[5], (B, H, hd, hd))
    y_k, s_k = wkv_kernel(r, k, v, w, u, s0, s_block=blk, interpret=True)
    y_r, s_r = wkv_ref(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r), rtol=1e-4,
                               atol=1e-5)


def test_wkv_custom_vjp_matches_scan_grad():
    from repro.kernels.wkv import wkv, wkv_ref
    B, S, H, hd = 2, 24, 2, 4
    ks = jax.random.split(jax.random.key(9), 6)
    args = [jax.random.normal(ks[0], (B, S, H, hd)),
            jax.random.normal(ks[1], (B, S, H, hd)),
            jax.random.normal(ks[2], (B, S, H, hd)),
            jax.nn.sigmoid(jax.random.normal(ks[3], (B, S, H, hd))),
            jax.random.normal(ks[4], (H, hd)),
            jax.random.normal(ks[5], (B, H, hd, hd))]

    def f_kernel(*a):
        y, s = wkv(*a, 8, True)
        return (y ** 2).sum() + (s ** 2).sum()

    def f_ref(*a):
        y, s = wkv_ref(*a)
        return (y ** 2).sum() + (s ** 2).sum()

    g_k = jax.grad(f_kernel, argnums=tuple(range(6)))(*args)
    g_r = jax.grad(f_ref, argnums=tuple(range(6)))(*args)
    for a, b in zip(g_k, g_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-5)


# ---------------------------------------------------------------------------
# SSD kernel (Mamba2 chunked recurrence, VMEM-resident state + decay tiles)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,S,H,hd,N,chunk",
                         [(1, 16, 2, 4, 4, 8), (2, 37, 3, 8, 4, 16),
                          (2, 64, 1, 16, 8, 32)])
def test_ssd_kernel_sweep(B, S, H, hd, N, chunk):
    from repro.kernels.ssd import ssd_kernel, ssd_ref
    ks = jax.random.split(jax.random.key(B * S + N), 5)
    la = -jax.nn.softplus(jax.random.normal(ks[0], (B, S, H)))
    dtx = jax.random.normal(ks[1], (B, S, H, hd))
    Bf = jax.random.normal(ks[2], (B, S, N))
    Cf = jax.random.normal(ks[3], (B, S, N))
    h0 = jax.random.normal(ks[4], (B, H, hd, N))
    y_k, h_k = ssd_kernel(la, dtx, Bf, Cf, h0, chunk=chunk, interpret=True)
    y_r, h_r = ssd_ref(la, dtx, Bf, Cf, h0)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_r), rtol=1e-4,
                               atol=1e-5)


def test_ssd_custom_vjp_matches_scan_grad():
    from repro.kernels.ssd import ssd, ssd_ref
    B, S, H, hd, N = 2, 24, 2, 4, 4
    ks = jax.random.split(jax.random.key(5), 5)
    args = [-jax.nn.softplus(jax.random.normal(ks[0], (B, S, H))),
            jax.random.normal(ks[1], (B, S, H, hd)),
            jax.random.normal(ks[2], (B, S, N)),
            jax.random.normal(ks[3], (B, S, N)),
            jax.random.normal(ks[4], (B, H, hd, N))]

    def loss(fn):
        def g(*a):
            y, h = fn(*a)
            return (y ** 2).sum() + (h ** 2).sum()
        return g

    g_k = jax.grad(loss(lambda *a: ssd(*a, 8, True)),
                   argnums=tuple(range(5)))(*args)
    g_r = jax.grad(loss(ssd_ref), argnums=tuple(range(5)))(*args)
    for a, b in zip(g_k, g_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-4)

# ---------------------------------------------------------------------------
# Fused single-step decode kernels (wkv_step / ssd_step): the
# fused-for-decode half of the chunk/fused pair.  Three-way parity —
# step kernel == jnp mirror (bitwise) == one step of the chunked scan.
# ---------------------------------------------------------------------------


def _wkv_step_inputs(seed, B, H, hd, S=None):
    shape = (B, H, hd) if S is None else (B, S, H, hd)
    ks = jax.random.split(jax.random.key(seed), 6)
    r, k, v = (jax.random.normal(ks[i], shape) for i in range(3))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], shape))
    u = jax.random.normal(ks[4], (H, hd))
    s0 = jax.random.normal(ks[5], (B, H, hd, hd))
    return r, k, v, w, u, s0


def _ssd_step_inputs(seed, B, H, hd, N, S=None):
    ks = jax.random.split(jax.random.key(seed), 5)
    t = () if S is None else (S,)
    la = -jax.nn.softplus(jax.random.normal(ks[0], (B, *t, H)))
    dtx = jax.random.normal(ks[1], (B, *t, H, hd))
    Bf = jax.random.normal(ks[2], (B, *t, N))
    Cf = jax.random.normal(ks[3], (B, *t, N))
    h0 = jax.random.normal(ks[4], (B, H, hd, N))
    return la, dtx, Bf, Cf, h0


@pytest.mark.parametrize("B,H,hd", [(1, 2, 4), (3, 3, 8), (5, 1, 16)])
def test_wkv_step_kernel_bitwise_vs_mirror(B, H, hd):
    """The fused step kernel (interpret: the exact TPU program) is
    bit-identical to its jitted jnp mirror — same multiply+sum op
    sequence, no einsum relowering."""
    from repro.kernels.wkv import wkv_step_kernel
    r, k, v, w, u, s0 = _wkv_step_inputs(B * hd + 1, B, H, hd)
    y_k, s_k = wkv_step_kernel(r, k, v, w, u, s0, interpret=True)
    y_r, s_r = jax.jit(ref.wkv_step_ref)(r, k, v, w, u, s0)
    assert np.array_equal(np.asarray(y_k), np.asarray(y_r))
    assert np.array_equal(np.asarray(s_k), np.asarray(s_r))


@pytest.mark.parametrize("B,H,hd,N", [(1, 2, 4, 4), (3, 1, 8, 4),
                                      (2, 3, 16, 8)])
def test_ssd_step_kernel_bitwise_vs_mirror(B, H, hd, N):
    from repro.kernels.ssd import ssd_step_kernel
    la, dtx, Bf, Cf, h0 = _ssd_step_inputs(B + N, B, H, hd, N)
    y_k, h_k = ssd_step_kernel(la, dtx, Bf, Cf, h0, interpret=True)
    y_r, h_r = jax.jit(ref.ssd_step_ref)(la, dtx, Bf, Cf, h0)
    assert np.array_equal(np.asarray(y_k), np.asarray(y_r))
    assert np.array_equal(np.asarray(h_k), np.asarray(h_r))


@pytest.mark.parametrize("S,blk", [(8, 8), (13, 8), (16, 4), (37, 16)])
def test_wkv_step_splices_onto_chunked_scan(S, blk):
    """Stepping the fused kernel S times reproduces the chunked-scan
    kernel's outputs and final state — including S % s_block != 0 edges,
    where the chunked kernel pads the last block (identity-masked tail).
    This is the serving contract: chunk-for-prefill then fused-for-decode
    continues the same sequence."""
    from repro.kernels.wkv import wkv_kernel
    B, H, hd = 2, 2, 8
    r, k, v, w, u, s0 = _wkv_step_inputs(S * blk, B, H, hd, S=S)
    y_c, s_c = wkv_kernel(r, k, v, w, u, s0, s_block=blk, interpret=True)
    step = jax.jit(ref.wkv_step_ref)
    s = s0
    ys = []
    for t in range(S):
        y, s = step(r[:, t], k[:, t], v[:, t], w[:, t], u, s)
        ys.append(y)
    np.testing.assert_allclose(np.asarray(jnp.stack(ys, 1)),
                               np.asarray(y_c), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_c), rtol=1e-4,
                               atol=1e-5)


@pytest.mark.parametrize("S,chunk", [(8, 8), (13, 8), (16, 4), (37, 16)])
def test_ssd_step_splices_onto_chunked_scan(S, chunk):
    from repro.kernels.ssd import ssd_kernel
    B, H, hd, N = 2, 2, 8, 4
    la, dtx, Bf, Cf, h0 = _ssd_step_inputs(S + chunk, B, H, hd, N, S=S)
    y_c, h_c = ssd_kernel(la, dtx, Bf, Cf, h0, chunk=chunk, interpret=True)
    step = jax.jit(ref.ssd_step_ref)
    h = h0
    ys = []
    for t in range(S):
        y, h = step(la[:, t], dtx[:, t], Bf[:, t], Cf[:, t], h)
        ys.append(y)
    np.testing.assert_allclose(np.asarray(jnp.stack(ys, 1)),
                               np.asarray(y_c), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_c), rtol=1e-4,
                               atol=1e-5)


def test_step_ops_dispatch_to_mirror_on_cpu():
    """``ops.wkv_step``/``ops.ssd_step`` fall back to the jnp mirrors off
    TPU — results identical to calling the refs directly (the dispatch
    itself must not perturb bits)."""
    B, H, hd, N = 2, 2, 4, 4
    r, k, v, w, u, s0 = _wkv_step_inputs(3, B, H, hd)
    y_o, s_o = ops.wkv_step(r, k, v, w, u, s0)
    y_r, s_r = jax.jit(ref.wkv_step_ref)(r, k, v, w, u, s0)
    assert np.array_equal(np.asarray(y_o), np.asarray(y_r))
    assert np.array_equal(np.asarray(s_o), np.asarray(s_r))
    la, dtx, Bf, Cf, h0 = _ssd_step_inputs(4, B, H, hd, N)
    y_o, h_o = ops.ssd_step(la, dtx, Bf, Cf, h0)
    y_r, h_r = jax.jit(ref.ssd_step_ref)(la, dtx, Bf, Cf, h0)
    assert np.array_equal(np.asarray(y_o), np.asarray(y_r))
    assert np.array_equal(np.asarray(h_o), np.asarray(h_r))


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**16), b=st.integers(1, 4),
       hd=st.sampled_from([4, 8]))
def test_wkv_step_property_state_carries(seed, b, hd):
    """Property: the fused step is shape-stable and associative with the
    scan — two single steps equal one two-token chunked scan."""
    from repro.kernels.wkv import wkv_ref
    H = 2
    r, k, v, w, u, s0 = _wkv_step_inputs(seed, b, H, hd, S=2)
    step = jax.jit(ref.wkv_step_ref)
    y0, s1 = step(r[:, 0], k[:, 0], v[:, 0], w[:, 0], u, s0)
    y1, s2 = step(r[:, 1], k[:, 1], v[:, 1], w[:, 1], u, s1)
    y_r, s_r = wkv_ref(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(jnp.stack([y0, y1], 1)),
                               np.asarray(y_r), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_r), rtol=1e-5,
                               atol=1e-6)


def test_kernel_mode_dispatch(monkeypatch):
    """CPU runs the mirrors, TPU the Mosaic kernels, the interpreter only
    on request; any other backend raises instead of falling back."""
    import jax
    assert ops.kernel_mode() == "mirror"            # this suite runs on CPU
    assert ops.kernel_mode(True) == "interpret"
    assert ops.kernel_mode(False) == "mosaic"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops.kernel_mode() == "mosaic"
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        ops.kernel_mode()
