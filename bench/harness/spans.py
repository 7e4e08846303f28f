"""The serving program's own records in a profiler trace: its host spans
(``serve.*``, from ``Scheduler.run_stream``), the JAX name-stack scope of
each device operation (``spec_draft``, ``spec_target``, ... from
``engine.make_spec_step``), and the per-round readings built on them.

Host spans and device events share the trace's clock.  A span's
arguments (``TraceAnnotation(name, uid=3)``) reach ``ProfileData`` as the
event's stats.  An operation's scope is the ``tf_op`` stat of its event
metadata on the device plane, which ``ProfileData`` does not expose: a
small decoder of the ``XSpace`` wire format reads it from the trace's
bytes (never ``tensorflow``, which is slow to import and must not share
the process that holds the chip).
"""
from __future__ import annotations

import dataclasses
import statistics
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from harness import trace as T

ROUND = "serve.round"
# the host work of a round: the round less its snapshot (the one
# device_get, which waits for the decode loop when overlap is off)
HOST_WORK = ("serve.prefill", "serve.pages", "serve.dispatch",
             "serve.stream", "serve.flush", "serve.admit")
LOOP_SCOPE = "jit(loop)/"       # the decode loop's operations
# the spec step's scopes (engine.make_spec_step), the re-feed first: it
# nests inside spec_commit
PHASES = ("spec_draft_refeed", "spec_draft", "spec_target", "spec_tail",
          "spec_stats", "spec_commit")
DRAFT = ("spec_draft", "spec_draft_refeed")


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: int                  # ns, the trace's clock
    end: int
    args: Tuple[Tuple[str, object], ...] = ()

    @property
    def uids(self) -> List[int]:
        """The requests the span served (``uid``, or ``uids`` separated by
        spaces)."""
        a = dict(self.args)
        v = a.get("uid", a.get("uids"))
        return [] if v is None else [int(u) for u in str(v).split()]


def host_spans(pd) -> List[Span]:
    """The program's (``serve.*``) and the benchmark's (``bench.*``) host
    spans, by start."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("serve.", "bench.")):
                    a = int(ev.start_ns)
                    out.append(Span(ev.name, a, a + int(ev.duration_ns),
                                    tuple(ev.stats)))
    return sorted(out, key=lambda s: s.start)


# -- XSpace wire format ------------------------------------------------------
# XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 (map<int64,
# XEventMetadata>: key 1, value 2), .stat_metadata = 5 (map<int64,
# XStatMetadata>); XEventMetadata.name = 2, .stats = 5; XStatMetadata.id =
# 1, .name = 2; XStat.metadata_id = 1, .str_value = 5, .ref_value = 7 (the
# id of a stat metadata whose name is the string).

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        c = buf[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, lo: int, hi: int
            ) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message in ``buf[lo:hi]``: an int for
    a varint, a (lo, hi) span of ``buf`` for a length-delimited field."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"unsupported wire type {wire} in XSpace")
        yield key >> 3, v


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def op_scopes(xspace: bytes, plane: str) -> Dict[str, str]:
    """For the device plane ``plane``: each operation's event name (its
    HLO text, as ``ProfileData`` names the event) -> its ``tf_op``, the
    JAX name stack it was traced under (e.g. ``jit(loop)/while/body/
    spec_draft/...``).  A name met twice with two stacks maps to ""."""
    for f, p in _fields(xspace, 0, len(xspace)):
        if f != 1:
            continue
        fields = list(_fields(xspace, *p))
        if any(k == 2 and _text(xspace, v) == plane for k, v in fields):
            break
    else:
        raise KeyError(f"no plane {plane!r} in the trace")
    stat_names: Dict[int, str] = {}
    metas = []
    for k, v in fields:
        if k not in (4, 5):
            continue
        entry = dict(_fields(xspace, *v))
        if 2 not in entry:
            continue
        body = list(_fields(xspace, *entry[2]))
        if k == 5:
            sm = dict(body)
            stat_names[sm.get(1, 0)] = _text(xspace, sm.get(2, (0, 0)))
        else:
            metas.append(body)
    tf_op = next((i for i, n in stat_names.items() if n == "tf_op"), None)
    out: Dict[str, str] = {}
    for body in metas:
        name, scope = None, None
        for k, v in body:
            if k == 2:
                name = _text(xspace, v)
            elif k == 5:
                st = dict(_fields(xspace, *v))
                if st.get(1) == tf_op:
                    scope = (_text(xspace, st[5]) if 5 in st
                             else stat_names.get(st.get(7), ""))
        if name is not None and scope is not None:
            out[name] = scope if out.get(name, scope) == scope else ""
    return out


def scoped_ops(pd, scopes: Dict[str, str], plane: str, t0: int, t1: int
               ) -> List[Tuple[str, str, int, int]]:
    """(short name, scope, start, end) of the leaf operations on
    ``plane``'s ops line, clipped to [t0, t1]: control-flow operations,
    whose events span their bodies, are left out, as ``top_ops`` does."""
    dev = next(p for p in pd.planes if p.name == plane)
    line = next(ln for ln in dev.lines if ln.name == T.OPS_LINE)
    out = []
    for ev in line.events:
        a = int(ev.start_ns)
        b = a + int(ev.duration_ns)
        name = T.short(ev.name)
        if b <= t0 or a >= t1 or name.startswith(
                ("while", "conditional", "call")):
            continue
        out.append((name, scopes.get(ev.name, ""), max(a, t0), min(b, t1)))
    return out


# -- readings ----------------------------------------------------------------

def window(red: T.Reduced, spans: Sequence[Span]) -> T.Interval:
    """The traced window: from the first to the last event, device or
    host (``red``'s window spans the device's events only, and a trace
    opened between rounds starts with host work)."""
    return (min([red.t0] + [s.start for s in spans]),
            max([red.t1] + [s.end for s in spans]))


def rounds(spans: Sequence[Span], t0: int, t1: int) -> List[Span]:
    """The ``serve.round`` spans wholly inside [t0, t1]."""
    return [s for s in spans if s.name == ROUND and t0 <= s.start
            and s.end <= t1]


def inside(spans: Sequence[Span], r: Span) -> List[Span]:
    """The spans that lie within ``r`` (``r`` among them)."""
    return [s for s in spans if r.start <= s.start and s.end <= r.end]


def round_idle_ms(spans: Sequence[Span], busy: Sequence[T.Interval],
                  t0: int, t1: int) -> Optional[float]:
    """Median over whole rounds of the device idle time inside each
    (``busy``: the device's merged operation intervals)."""
    vals = []
    for r in rounds(spans, t0, t1):
        on = sum(min(b, r.end) - max(a, r.start) for a, b in busy
                 if b > r.start and a < r.end)
        vals.append((r.end - r.start - on) * 1e-6)
    return statistics.median(vals) if vals else None


def host_round_ms(spans: Sequence[Span], t0: int, t1: int
                  ) -> Optional[float]:
    """Median over whole rounds of the host time in the round's own work
    (``HOST_WORK`` spans; ``serve.stream`` includes the time the consumer
    holds each token)."""
    vals = [sum(s.end - s.start for s in inside(spans, r)
                if s.name in HOST_WORK) * 1e-6
            for r in rounds(spans, t0, t1)]
    return statistics.median(vals) if vals else None


def phase_seconds(ops: Sequence[Tuple[str, str, int, int]]
                  ) -> Dict[str, float]:
    """Device seconds of the decode loop's operations by the spec-step
    phase whose scope names them (``other``: the loop's own commit of the
    step's outputs, outside the step)."""
    out: Dict[str, float] = {}
    for _, scope, a, b in ops:
        if not scope.startswith(LOOP_SCOPE):
            continue
        parts = set(scope.split("/"))
        phase = next((p for p in PHASES if p in parts), "other")
        out[phase] = out.get(phase, 0.0) + (b - a) * 1e-9
    return out


def draft_share(ops: Sequence[Tuple[str, str, int, int]]
                ) -> Optional[float]:
    """Device time of the draft model in the decode loop (``spec_draft``:
    propose; ``spec_draft_refeed``: the commit's re-feed) over that of all
    the loop's operations, in %."""
    sec = phase_seconds(ops)
    total = sum(sec.values())
    if total <= 0 or set(sec) <= {"other"}:     # no scoped step traced
        return None
    return 100.0 * sum(sec.get(p, 0.0) for p in DRAFT) / total


def slot_occupancy(stats0: Dict[str, float], stats1: Dict[str, float],
                   batch: int) -> Optional[float]:
    """Alive slot-steps over all slot-steps the loop ran between two
    ``Scheduler.stats()`` readings, in %."""
    if "live_slot_steps" not in stats0:
        return None
    steps = stats1["loop_steps"] - stats0["loop_steps"]
    if steps <= 0:
        return None
    live = stats1["live_slot_steps"] - stats0["live_slot_steps"]
    return 100.0 * live / (batch * steps)


def idle_gaps(busy: Sequence[T.Interval], t0: int, t1: int
              ) -> List[T.Interval]:
    """The device's idle intervals in [t0, t1], longest first."""
    gaps, prev = [], t0
    for a, b in busy:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    if t1 > prev:
        gaps.append((prev, t1))
    return sorted(gaps, key=lambda g: g[0] - g[1])


def gap_name(gap: T.Interval, spans: Sequence[Span]) -> str:
    """The innermost span that covers the gap's middle: a ``serve.*``
    span before a ``bench.*`` one, the shortest first; ``host idle``
    where none does."""
    mid = (gap[0] + gap[1]) // 2
    cover = [s for s in spans if s.start <= mid < s.end]
    if not cover:
        return "host idle"
    best = min(cover, key=lambda s: (not s.name.startswith("serve."),
                                     s.end - s.start))
    return best.name
