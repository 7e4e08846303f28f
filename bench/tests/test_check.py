"""The check that decides ``correct``: the plain reference agrees with the
program, the served path passes at a tiny size, and the control and each
fault a serving cell can have come out as not correct."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tinycell import tiny_cell

# the tiny configuration's limits (tiny.json) come from its readings on
# the CPU over samples of up to 430 served tokens in 5 s windows, seeds
# 2-7 of each scheme: the mean token gap of the program reads at most
# 3.6e-4 nats, the control's 0.0030 and more (limit 0.002); the mean
# accept gap of the program at most 4e-5, the control's 2.6e-4 and more
# (limit 1e-4)


# a window long enough that a loaded CPU still finishes the check's
# sample of served tokens
SECONDS = 5.0


def _result(bench_run, cell, seed, **kw):
    lines = []
    rc = bench_run.run(cell, seed, SECONDS, False, require_tpu=False,
                       out=lines.append, cache=False, **kw)
    assert rc == 0 and len(lines) == 1
    return json.loads(lines[0])


def test_reference_matches_program_forward():
    """The reference's float32 logits equal the program's own dense
    forward on the same float32 weights, to float32 rounding."""
    from harness import reference, serve
    from harness.weights import make_params
    from repro.models import model as M
    cell = tiny_cell()
    tcfg, _, _ = serve.program_configs(cell)
    params = make_params(cell.config, 3, 0, jnp.float32)
    toks = np.random.default_rng(0).integers(1, 384, size=40).astype(
        np.int32)
    want = M.forward(params, tcfg, {"tokens": jnp.asarray(toks)[None]})[0][0]
    got = reference.logits(params, cell.config, toks, 0, 40, 40)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("watermark", ["gumbel", "synthid"])
def test_served_path_is_correct(bench_run, watermark):
    cell = tiny_cell(watermark)
    res = _result(bench_run, cell, 5)
    assert res["correct"], res["checked"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["checked"]) == {"token_gap_mean", "accept_gap_mean",
                                   "failed_requests"}
    assert list(res)[-1] == "checked"
    assert set(res["metrics"]) == {m["name"]
                                   for m in cell.reported("end_to_end")}
    assert {"tokens_per_s", "setup_s"} <= set(res["metrics"])


@pytest.mark.parametrize("seed", [2, 3])
def test_control_fails_where_the_program_passes(bench_run, seed):
    """The reference in float8 weights, put in the program's place, is not
    correct by the limits that the program's run is within."""
    from harness.serve import Compiles
    cell = tiny_cell()
    bench_run.setup_jax(cache=False)
    compiles = Compiles()
    try:
        r = bench_run.serve_once(cell, seed, SECONDS, False, compiles,
                                 jax.devices()[0])
    finally:
        compiles.close()
    rd = bench_run.check(cell, r, seed, control=True)
    limits = cell.config["check"]["limits"]
    assert bench_run.judge(rd, limits)[0]
    assert not bench_run.judge(rd, limits, "control_")[0]


def _faulty_step(fault):
    from repro.serve import engine as E
    orig = E.make_spec_step

    def make(tcfg, dcfg, scfg, mesh=None):
        step = orig(tcfg, dcfg, scfg, mesh=mesh)

        def faulty(t_params, d_params, state, live=None, eos_id=None):
            new, out = step(t_params, d_params, state, live=live,
                            eos_id=eos_id)
            if fault == "token_altered":
                out = out._replace(
                    out_tokens=(out.out_tokens + 1) % tcfg.vocab)
            elif fault == "state_unchanged":
                new = state
            elif fault == "half_batch_left_out":
                B = out.out_len.shape[0]
                out = out._replace(out_len=jnp.where(
                    jnp.arange(B) >= B // 2, 0, out.out_len))
            return new, out
        return faulty
    return make


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged",
                                   "half_batch_left_out"])
def test_fault_in_timed_path_is_not_correct(bench_run, monkeypatch, fault):
    from repro.serve import engine as E
    E._jitted_gen_loop_plain.cache_clear()
    monkeypatch.setattr(E, "make_spec_step", _faulty_step(fault))
    try:
        res = _result(bench_run, tiny_cell(), 4, drain_s=3.0)
    finally:
        E._jitted_gen_loop_plain.cache_clear()
    assert res["correct"] is False


def _residual_position_with_a_uniform_of_one(key=12345, V=4096):
    """A context whose target-stream uniforms put exactly 1 on a token
    below ``V``, and that token."""
    from harness import prf
    ctx = np.arange(1, 20001, dtype=np.uint32)
    u = np.asarray(jax.jit(lambda c: prf.uniform(
        prf.seed(jnp.uint32(key), prf.TARGET, c)[:, None],
        jnp.arange(V, dtype=jnp.uint32)[None]))(jnp.asarray(ctx)))
    i, w = np.argwhere(u >= 1.0)[0]
    return int(ctx[i]), int(w)


@pytest.mark.parametrize("ratio, gap_is_finite", [(1.1, True), (3.0, False)])
def test_uniform_of_one_sets_the_best_key_only_where_the_residual_is_clear(
        ratio, gap_is_finite):
    """At a residual draw, a token keyed +inf by a uniform of 1 must win,
    unless its log(P/Q) lies within RATIO_MARGIN of 0, where rounding may
    have left the program's residual at 0: serving another token is then
    no fault."""
    from harness import check as C
    key, V, T = 12345, 4096, 0.7
    ctx, w = _residual_position_with_a_uniform_of_one(key, V)
    rng = np.random.default_rng(0)
    pl = rng.normal(size=(V,)).astype(np.float32)
    ql = pl + rng.normal(size=(V,)).astype(np.float32)

    def soft(x):
        e = np.exp((x - x.max()) / T)
        return e / e.sum()

    for _ in range(4):      # P / Q at the token to ``ratio``
        ql[w] += T * np.log(soft(pl)[w] / ratio / soft(ql)[w])
    assert abs(soft(pl)[w] / soft(ql)[w] - ratio) < 1e-3 * ratio
    kind = np.array([C.RESID], np.int32)
    seeds = C._seeds(key, np.array([ctx], np.uint32), kind)
    static = dict(m=30, tournament=False, temperature=T)
    p, q, dist = C._dists(jnp.asarray(pl)[None], jnp.asarray(ql)[None],
                          jnp.asarray(kind), T)
    assert 0 < float(dist[0, w])
    # the served token: the race's winner among the other tokens
    keys = C._race_keys(dist, *seeds[:3], jnp.zeros((1,), bool),
                        **{k: static[k] for k in ("m", "tournament")})[0]
    served = int(jnp.argmax(keys.at[0, w].set(-jnp.inf)[0]))
    gap = C._gaps(jnp.asarray(pl)[None], jnp.asarray(ql)[None],
                  jnp.asarray([served], jnp.int32), jnp.asarray(kind),
                  jnp.zeros((1,), bool), jnp.asarray([0.5], jnp.float32),
                  seeds, **static)[0]
    assert bool(jnp.isfinite(gap[0])) == gap_is_finite
