"""The program's own spans, scopes and counters as the harness reads them
(``harness/spans.py``): by hand on built inputs, and on a trace of the
yi-6b chat cell recorded on a TPU v5e chip with the ``serve.*`` spans and
the spec-step scopes (``data/serve.xplane.pb.gz``: 1.08 s holding one
whole sync round, recorded by ``trace_report.py --save`` without HLO
protos), read on the CPU."""
import gzip
import importlib.util
import os
import sys

import pytest

from harness import trace as T
# the trace reduction's own cases, run again on this recording (pytest
# gives them this module's ``reduced`` fixture)
from test_trace import (test_breakdown_lists,  # noqa: F401
                        test_device_busy_and_window,
                        test_kernels_and_programs_found)

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data", "serve.xplane.pb.gz")
PLANE = "/device:TPU:0"


def _load_spans():
    """``harness/spans.py`` by its path, as ``run.py`` loads its readers:
    the test process may hold another copy of the ``harness`` package."""
    spec = importlib.util.spec_from_file_location(
        "bench_harness_spans",
        os.path.join(os.path.dirname(HERE), "harness", "spans.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod        # its dataclass looks itself up
    spec.loader.exec_module(mod)
    return mod


SP = _load_spans()


@pytest.fixture(scope="module")
def raw():
    with gzip.open(DATA, "rb") as f:
        return f.read()


@pytest.fixture(scope="module")
def reduced(raw):
    from jax.profiler import ProfileData
    pd = ProfileData.from_serialized_xspace(raw)
    return pd, T.reduce(pd)


@pytest.fixture(scope="module")
def spans(reduced):
    return SP.host_spans(reduced[0])


@pytest.fixture(scope="module")
def ops(raw, reduced):
    pd, red = reduced
    return SP.scoped_ops(pd, SP.op_scopes(raw, PLANE), PLANE, red.t0,
                         red.t1)


# -- by hand -----------------------------------------------------------------

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields):
    """A protobuf message from (field, int | bytes | str) pairs."""
    out = bytearray()
    for f, v in fields:
        if isinstance(v, int):
            out += _varint(f << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(f << 3 | 2) + _varint(len(v)) + v
    return bytes(out)


def _meta(mid, name, *stats):
    body = _msg((1, mid), (2, name), *((5, _msg(*s)) for s in stats))
    return (4, _msg((1, mid), (2, body)))


def test_op_scopes_by_hand():
    """The decoder reads ``tf_op`` as a string or a reference to a stat
    name, on the named plane only; a name met under two stacks maps to
    ""."""
    stat = lambda sid, name: (5, _msg((1, sid), (2, _msg((1, sid),  # noqa
                                                         (2, name)))))
    dev = _msg((2, PLANE), stat(7, "tf_op"),
               stat(9, "jit(loop)/while/body/spec_target/dot"),
               _meta(1, "%fusion.1 = f32[2]", ((1, 7), (5, "jit(loop)/"
                                                        "spec_draft/add"))),
               _meta(2, "%dot.3 = f32[2]", ((1, 7), (7, 9))),
               _meta(3, "%copy.2 = f32[2]", ((1, 7), (5, "jit(a)/x"))),
               _meta(4, "%copy.2 = f32[2]", ((1, 7), (5, "jit(b)/x"))),
               _meta(5, "%iota.1 = s32[2]", ((1, 8), (5, "other"))))
    host = _msg((2, "/host:CPU"),
                _meta(1, "%fusion.1 = f32[2]", ((1, 7), (5, "host"))))
    space = _msg((1, host), (1, dev), (4, "hostname"))
    assert SP.op_scopes(space, PLANE) == {
        "%fusion.1 = f32[2]": "jit(loop)/spec_draft/add",
        "%dot.3 = f32[2]": "jit(loop)/while/body/spec_target/dot",
        "%copy.2 = f32[2]": ""}
    with pytest.raises(KeyError):
        SP.op_scopes(space, "/device:TPU:1")


def test_phases_and_draft_share_by_hand():
    loop = "jit(loop)/while/body/"
    ops = [("a", loop + "spec_draft/while/body/x", 0, 30),
           ("b", loop + "spec_commit/spec_draft_refeed/y", 30, 40),
           ("c", loop + "spec_target/z", 40, 90),
           ("d", loop + "spec_commit/w", 90, 95),
           ("e", loop + "dynamic-update-slice", 95, 100),
           ("f", "jit(_chunk_fn)/spec_draft/q", 100, 200)]
    sec = SP.phase_seconds(ops)
    assert sec == pytest.approx({"spec_draft": 30e-9,
                                 "spec_draft_refeed": 10e-9,
                                 "spec_target": 50e-9, "spec_commit": 5e-9,
                                 "other": 5e-9})
    assert SP.draft_share(ops) == pytest.approx(40.0)
    # a loop traced without the scopes reads nothing
    assert SP.draft_share([("e", loop + "add", 0, 10)]) is None


def test_gap_named_by_innermost_span():
    """A gap covered by ``serve.snapshot`` inside ``bench.run_stream``
    reads ``serve.snapshot``; a ``serve.*`` span comes before a
    ``bench.*`` one, the shortest first."""
    spans = [SP.Span("bench.run_stream", 0, 100),
             SP.Span("serve.round", 10, 90),
             SP.Span("serve.snapshot", 20, 60),
             SP.Span("bench.submit", 62, 64)]
    assert SP.gap_name((30, 50), spans) == "serve.snapshot"
    assert SP.gap_name((61, 65), spans) == "serve.round"
    assert SP.gap_name((95, 99), spans) == "bench.run_stream"
    assert SP.gap_name((200, 210), spans) == "host idle"
    assert SP.idle_gaps([(0, 10), (12, 20), (30, 35)], 0, 40) == \
        [(20, 30), (35, 40), (10, 12)]


def test_round_readings_by_hand():
    spans = [SP.Span("serve.round", 0, 100_000_000, (("round", 1),)),
             SP.Span("serve.dispatch", 0, 2_000_000),
             SP.Span("serve.snapshot", 2_000_000, 90_000_000),
             SP.Span("serve.stream", 90_000_000, 96_000_000),
             SP.Span("serve.flush", 96_000_000, 97_000_000,
                     (("uids", "3 4"),)),
             SP.Span("serve.admit", 97_000_000, 100_000_000,
                     (("uids", 5),)),
             SP.Span("serve.round", 100_000_000, 300_000_000)]
    busy = [(1_000_000, 91_000_000)]
    # the second round runs past the window: only the first is whole
    assert SP.rounds(spans, 0, 200_000_000) == spans[:1]
    assert SP.round_idle_ms(spans, busy, 0, 200_000_000) == \
        pytest.approx(10.0)
    assert SP.host_round_ms(spans, 0, 200_000_000) == pytest.approx(12.0)
    assert spans[4].uids == [3, 4] and spans[5].uids == [5]
    assert SP.round_idle_ms(spans, busy, 0, 50) is None


def test_slot_occupancy_by_hand():
    s0 = {"loop_steps": 100.0, "live_slot_steps": 250.0}
    s1 = {"loop_steps": 300.0, "live_slot_steps": 850.0}
    assert SP.slot_occupancy(s0, s1, 4) == pytest.approx(75.0)
    # stats() without the live counters (an older program) reads nothing
    assert SP.slot_occupancy({"alive_slot_steps": 1.0}, s1, 4) is None
    assert SP.slot_occupancy(s0, s0, 4) is None


# -- on the recorded trace ----------------------------------------------------

def test_recording_holds_whole_rounds(reduced, spans):
    _, red = reduced
    rounds = SP.rounds(spans, *SP.window(red, spans))
    assert rounds
    for r in rounds:
        names = {s.name for s in SP.inside(spans, r)}
        assert {"serve.dispatch", "serve.snapshot", "serve.stream",
                "serve.flush", "serve.admit", "serve.pages"} <= names
    assert all(s.uids for s in spans if s.name == "serve.prefill")


def test_paged_attention_scoped(reduced, ops):
    """Every paged attention call runs under the draft's (propose or
    re-feed) or the target's scope in the decode loop, or in the prefill
    program; the draft takes a share of the loop strictly between 0 and
    100%."""
    pa = [(n, s) for n, s, _, _ in ops if n.startswith("paged_attention")]
    assert pa
    for name, scope in pa:
        parts = scope.split("/")
        assert scope.startswith("jit(_chunk_fn)/") or (
            scope.startswith(SP.LOOP_SCOPE)
            and ({"spec_draft", "spec_draft_refeed", "spec_target"}
                 & set(parts))), (name, scope)
    assert 0 < SP.draft_share(ops) < 100
    sec = SP.phase_seconds(ops)
    assert {"spec_draft", "spec_draft_refeed", "spec_target"} <= set(sec)


def test_host_and_device_share_a_clock(reduced, spans):
    """Each decode-loop program starts after the ``serve.dispatch`` span
    that launched it began, and ends before the ``serve.snapshot`` that
    waits for it ends."""
    _, red = reduced
    loops = [(a, b) for d in red.devices.values()
             for n, a, b in d["modules"] if n.startswith("jit_loop")
             and red.t0 < a and b < red.t1]
    assert loops
    dispatch = [s for s in spans if s.name == "serve.dispatch"]
    snapshot = [s for s in spans if s.name == "serve.snapshot"]
    for a, b in loops:
        launch = max((s for s in dispatch if s.start <= a),
                     key=lambda s: s.start)
        wait = min((s for s in snapshot if s.start >= launch.end),
                   key=lambda s: s.start)
        assert launch.start <= a and b <= wait.end, (a, b, launch, wait)


def test_round_gaps_carry_program_names(reduced, spans):
    """Every device idle gap of 1 ms or more inside a whole round is named
    by a ``serve.*`` span, not by the benchmark's ``bench.run_stream``;
    the readings built on the rounds are finite and positive."""
    _, red = reduced
    busy = red.busy_intervals(PLANE)
    rounds = SP.rounds(spans, *SP.window(red, spans))
    gaps = [g for g in SP.idle_gaps(busy, *SP.window(red, spans))
            if g[1] - g[0] >= 1_000_000
            and any(r.start <= g[0] and g[1] <= r.end for r in rounds)]
    assert gaps
    assert all(SP.gap_name(g, spans).startswith("serve.") for g in gaps)
    t0, t1 = SP.window(red, spans)
    assert SP.round_idle_ms(spans, busy, t0, t1) > 0
    assert SP.host_round_ms(spans, t0, t1) > 0
