"""What the serving program records for a profiler: the ``serve.*`` host
spans of ``Scheduler.run_stream`` (nested per sync round, tagged with the
requests they serve), the live counters of ``Scheduler.stats()`` (kept
from each round's snapshot, equal to the flush-time counts once every
request is served), and the spec step's named scopes (metadata only: the
step lowers to the same program without them).

Each case runs as this file's ``__main__`` in a fresh interpreter on this
checkout's ``src`` (``_run``): the test process may already hold another
copy of the ``repro`` package, imported through another path.
"""
import contextlib
import glob
import os
import subprocess
import sys
import tempfile

import pytest

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src")
PAGED = dict(page_size=4, num_pages=96, prefill_chunk=4)
CHILDREN = ("serve.prefill", "serve.pages", "serve.dispatch",
            "serve.snapshot", "serve.stream", "serve.flush", "serve.admit")


def _run(*case):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, os.path.abspath(__file__),
                          *case], env=env, capture_output=True, text=True,
                         timeout=900)
    assert out.returncode == 0, f"\n--- stdout ---\n{out.stdout}" \
                                f"\n--- stderr ---\n{out.stderr}"
    assert f"CASE OK {' '.join(case)}" in out.stdout, out.stdout


def test_round_spans_nest_every_phase():
    """Under a trace, every sync round is one ``serve.round`` span (with
    its number) holding each phase's span; prefill spans carry their
    request and chunk, flush and admit spans the requests they served."""
    _run("spans")


@pytest.mark.parametrize("paged,overlap", [(False, False), (True, True)])
def test_live_counters_match_flush_counts(paged, overlap):
    """The live counters move while requests run and, once every request
    has flushed, equal the flush-time counts; ``loop_steps`` is the loop's
    own step counter, and submit times are dropped at flush."""
    _run("counters", str(int(paged)), str(int(overlap)))


def test_spec_scopes_are_metadata_only():
    """The spec step's phases carry their scope in the operations' JAX
    name stack, and without the scopes the step lowers to the same
    program (its text without locations)."""
    _run("scopes")


# ---------------------------------------------------------------------------
# Subprocess bodies.
# ---------------------------------------------------------------------------


def _scheduler(paged=True, overlap=False):
    import jax
    from repro.serve import engine as E
    from repro.serve.scheduler import Scheduler
    tcfg, dcfg, tp, dp = _make_pair()
    scfg = E.SpecConfig(K=2, watermark="gumbel")
    sched = Scheduler(tp, dp, tcfg, dcfg, scfg, batch=2,
                      key=jax.random.key(1234), max_tokens=8, sync_every=2,
                      overlap=overlap, **(PAGED if paged else {}))
    for prompt, n in _schedule(5, 5, lo=3, hi=8, plen_lo=4, plen_hi=9):
        sched.submit(prompt, n)
    return sched


def _case_spans():
    import jax
    from jax.profiler import ProfileData
    sched = _scheduler()
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            results = sched.run()
        path = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                      "*.xplane.pb"))[0]
        pd = ProfileData.from_file(path)
        spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                  dict(ev.stats))
                 for p in pd.planes if p.name.startswith("/host:")
                 for ln in p.lines for ev in ln.events
                 if ev.name.startswith("serve.")]
    rounds = sorted((s for s in spans if s[0] == "serve.round"),
                    key=lambda s: s[1])
    assert [s[3]["round"] for s in rounds] == \
        list(range(1, sched.n_rounds + 1))
    # the admission before the first round is the one span outside a round
    first = min(s[1] for s in rounds)
    outside = [s for s in spans if s[0] != "serve.round"
               and not any(r[1] <= s[1] and s[2] <= r[2] for r in rounds)]
    assert [(s[0], s[2] <= first) for s in outside] == [("serve.admit",
                                                         True)]
    assert set(CHILDREN) | {"serve.round"} == {s[0] for s in spans}
    for name, _, _, args in spans:
        if name == "serve.prefill":
            assert set(args) == {"uid", "chunk"}
    served = sorted(r.uid for r in results)

    def uids(name):
        return sorted(int(u) for s in spans if s[0] == name
                      for u in str(s[3].get("uids", "")).split())

    assert uids("serve.flush") == served
    assert uids("serve.admit") == served
    assert sorted({s[3]["uid"] for s in spans
                   if s[0] == "serve.prefill"}) == served


def _case_counters(paged, overlap):
    import jax
    sched = _scheduler(paged=paged, overlap=overlap)
    moved_early = False
    for _ in sched.run_stream():
        s = sched.stats()
        moved_early |= s["live_slot_steps"] > s["alive_slot_steps"]
    s = sched.stats()
    assert moved_early
    assert s["live_slot_steps"] == s["alive_slot_steps"] > 0
    assert abs(s["accepted_live"]
               - s["aatps"] * s["alive_slot_steps"]) < 1e-6
    assert s["loop_steps"] == int(jax.device_get(sched.carry["n_steps"]))
    assert s["rounds"] == sched.n_rounds
    assert s["live_slot_steps"] <= sched.B * s["loop_steps"]
    assert sched._t_submit == {}
    assert all(r.ttft_s is not None for r in sched.results.values())


def _case_scopes():
    import jax
    import jax.numpy as jnp
    from repro.serve import engine as E
    tcfg, dcfg, tp, dp = _make_pair()
    scfg = E.SpecConfig(K=2, watermark="gumbel")
    state = E.init_state(tp, dp, tcfg, dcfg, scfg,
                         jnp.ones((2, 5), jnp.int32), 64,
                         jax.random.key(1234))

    def lowered():
        step = E.make_spec_step(tcfg, dcfg, scfg)
        return jax.jit(step).lower(tp, dp, state)

    scoped = lowered()
    jax.named_scope = lambda name: contextlib.nullcontext()
    plain = lowered()
    names = scoped.as_text(debug_info=True)
    for scope in ("spec_draft/", "spec_target/", "spec_tail/",
                  "spec_stats/", "spec_commit/spec_draft_refeed/"):
        assert scope in names, scope
    assert "spec_draft/" not in plain.as_text(debug_info=True)
    assert scoped.as_text() == plain.as_text()


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from test_streaming import _make_pair, _schedule
    case, args = sys.argv[1], [bool(int(a)) for a in sys.argv[2:]]
    {"spans": _case_spans, "counters": _case_counters,
     "scopes": _case_scopes}[case](*args)
    print("CASE OK " + " ".join(sys.argv[1:]))
