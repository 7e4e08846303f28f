"""Detection layer: stat recovery, dedup, score normalization, selector
orderings on controlled synthetic records."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import prf
from repro.core.detection import gumbel_detect, records, synthid_detect
from repro.core.detection.records import SeqRecord
from repro.core.watermark import gumbel, synthid

KEY = jax.random.key(77)


def test_gumbel_recover_matches_sample():
    """The U value recovered at detection time equals the one used at
    sampling time (same key, context, stream)."""
    dec = gumbel.make()
    P = jax.nn.softmax(jax.random.normal(jax.random.key(1), (32,)))
    ctxs = jnp.arange(64, dtype=jnp.uint32)
    toks, ys = jax.vmap(lambda c: dec.sample(P, KEY, c,
                                             prf.STREAM_DRAFT))(ctxs)
    rec = dec.recover_stats(toks, KEY, ctxs, prf.STREAM_DRAFT, 32)
    np.testing.assert_allclose(np.asarray(ys), np.asarray(rec), rtol=1e-6)
    # watermarked stats concentrate near 1
    assert float(ys.mean()) > 0.75


def test_synthid_recover_matches_sample():
    dec = synthid.make(m=8)
    P = jax.nn.softmax(jax.random.normal(jax.random.key(2), (16,)))
    ctxs = jnp.arange(48, dtype=jnp.uint32)
    toks, ys = jax.vmap(lambda c: dec.sample(P, KEY, c,
                                             prf.STREAM_DRAFT))(ctxs)
    rec = dec.recover_stats(toks, KEY, ctxs, prf.STREAM_DRAFT, 16)
    np.testing.assert_allclose(np.asarray(ys), np.asarray(rec), atol=0)
    # tournament winners carry more ones
    assert float(ys.mean()) > 0.55


def _mk_record(n, bias_draft, src, seed=0, dup_frac=0.0):
    """Synthetic record: src follows StepOutput.from_draft semantics
    (1 = draft), so y_draft is biased toward 1 at src==1 positions."""
    rng = np.random.default_rng(seed)
    y_d = rng.uniform(size=n).astype(np.float32)
    y_t = rng.uniform(size=n).astype(np.float32)
    if bias_draft:
        y_d[src == 1] = 1.0 - (1.0 - y_d[src == 1]) * 0.55
        y_t[src == 0] = 1.0 - (1.0 - y_t[src == 0]) * 0.55
    u = np.where(src == 1, rng.uniform(0, 0.5, n),
                 rng.uniform(0.5, 1, n)).astype(np.float32)
    ctx = rng.integers(0, 2**32, n, dtype=np.uint32)
    if dup_frac:
        k = int(n * dup_frac)
        ctx[n - k:] = ctx[0]
    return SeqRecord(tokens=np.arange(n, dtype=np.int32), y_draft=y_d,
                     y_target=y_t, u=u, src=src.astype(np.int8),
                     watermarked=bias_draft, ctx=ctx)


def test_dedupe_drops_repeated_contexts():
    src = np.zeros(50, int)
    r = _mk_record(50, True, src, dup_frac=0.4)
    d = r.dedupe()
    assert len(d.tokens) == 30  # 20 positions share one ctx -> 19 dropped,
    #                             plus position 0 keeps the first occurrence
    assert len(np.unique(d.ctx)) == len(d.ctx)


def test_ars_zscore_null_centered():
    rng = np.random.default_rng(3)
    zs = [gumbel_detect.ars_score(rng.uniform(size=200)) for _ in range(200)]
    assert abs(np.mean(zs)) < 0.25
    assert 0.6 < np.std(zs) < 1.6


def test_selector_orderings_on_synthetic_records():
    """With perfectly informative u (u<0.5 iff draft), Ars-τ at τ=0.5 must
    match the oracle and beat the prior rule."""
    n = 60
    rng = np.random.default_rng(4)
    wm, null = [], []
    for i in range(40):
        src = (rng.uniform(size=n) < 0.6).astype(int)   # 1 = draft, ~60%
        wm.append(_mk_record(n, True, src, seed=i))
        null.append(_mk_record(n, False, src, seed=1000 + i))
    s_tau_wm = gumbel_detect.scores_tau(wm, 0.5, n)
    s_tau_null = gumbel_detect.scores_tau(null, 0.5, n)
    s_or_wm = gumbel_detect.scores_oracle(wm, n)
    s_or_null = gumbel_detect.scores_oracle(null, n)
    s_pr_wm = gumbel_detect.scores_prior(wm, 0.6, n)
    s_pr_null = gumbel_detect.scores_prior(null, 0.6, n)
    auc_tau = records.auc(s_tau_wm, s_tau_null)
    auc_or = records.auc(s_or_wm, s_or_null)
    auc_pr = records.auc(s_pr_wm, s_pr_null)
    # u is perfectly informative -> tau selection equals the oracle
    assert auc_tau == pytest.approx(auc_or, abs=1e-9)
    assert auc_tau > auc_pr + 0.02
    assert auc_or > 0.9


def test_calibrate_tau_finds_separator():
    n = 200
    rng = np.random.default_rng(5)
    wm = [_mk_record(n, True, (rng.uniform(size=n) > 0.5).astype(int),
                     seed=i) for i in range(20)]
    null = [_mk_record(n, False, (rng.uniform(size=n) > 0.5).astype(int),
                       seed=100 + i) for i in range(20)]
    tau = gumbel_detect.calibrate_tau(wm, null, n, grid=21)
    # the calibrated tau must do at least as well as the extremes
    def tpr(tt):
        return records.tpr_at_fpr(gumbel_detect.scores_tau(wm, tt, n),
                                  gumbel_detect.scores_tau(null, tt, n))
    assert tpr(tau) >= max(tpr(0.001), tpr(0.999)) - 1e-9


def test_tpr_at_fpr_bounds():
    wm = np.array([3.0, 4.0, 5.0, 6.0])
    null = np.array([0.0, 0.5, 1.0, 2.0])
    assert records.tpr_at_fpr(wm, null, 0.25) == 1.0
    assert records.tpr_at_fpr(null, wm, 0.01) == 0.0


def test_synthid_psi_fit_improves_likelihood():
    """fit_psi must beat the uniform model on tournament-biased g-values."""
    m = 6
    dec = synthid.make(m=m)
    P = jax.nn.softmax(jax.random.normal(jax.random.key(8), (12,)))
    ctxs = jnp.arange(600, dtype=jnp.uint32)
    _, ys = jax.vmap(lambda c: dec.sample(P, KEY, c,
                                          prf.STREAM_DRAFT))(ctxs)
    y = np.asarray(ys)
    psi = synthid_detect.fit_psi(y, m, steps=200)
    ll_fit = float(jnp.mean(synthid_detect.log_f1(psi, jnp.asarray(y))))
    ll_unif = float(m * np.log(0.5))
    assert ll_fit > ll_unif


@pytest.mark.parametrize("m", [0, 4], ids=["scalar-stat", "g-bit-stat"])
def test_stream_selection_broadcasts_over_vector_stats(m):
    """The per-token draft/target stream pick (tau, prior and oracle
    rules) selects whole (m,) g-bit rows for vector-stat schemes exactly as
    it selects scalars for gumbel."""
    rng = np.random.default_rng(0)
    n = 6
    shape = (n, m) if m else (n,)
    rec = SeqRecord(tokens=np.arange(n, dtype=np.int32),
                    y_draft=rng.uniform(size=shape),
                    y_target=rng.uniform(size=shape) + 2.0,
                    u=np.linspace(0.05, 0.95, n),
                    src=(np.arange(n) % 2).astype(np.int8),
                    watermarked=True)
    picks = {"tau": rec.u < 0.5, "oracle": rec.src == 1}
    got = {"tau": gumbel_detect.select_tau(rec, 0.5),
           "oracle": gumbel_detect.select_oracle(rec)}
    for rule, pick in picks.items():
        assert got[rule].shape == shape
        for t in range(n):
            want = rec.y_draft[t] if pick[t] else rec.y_target[t]
            np.testing.assert_array_equal(got[rule][t], want, err_msg=rule)
    prior = gumbel_detect.select_prior(rec, 0.5, np.random.default_rng(1))
    assert prior.shape == shape
