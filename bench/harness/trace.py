"""Device traces: capture one window with the JAX profiler and reduce it
to device busy time, device time per program and per kernel, and the
longest device idle gaps set against what the benchmark's host spans
were doing.

The profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it.  A TPU plane is named
``/device:TPU:<n>``; on it the line ``XLA Ops`` holds one event per
executed operation (a Pallas kernel appears under the ``name`` given to
its ``pallas_call``) and the line ``XLA Modules`` one event per executed
program (``jit_<function name>(...)``).  Host spans of the benchmark
(``jax.profiler.TraceAnnotation``) sit on the host plane's thread lines.
"""
from __future__ import annotations

import glob
import os
import shutil
import tempfile
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."


class Capture:
    """``start()`` / ``stop()`` a profiler trace into a fresh temporary
    directory; ``load()`` reads it back; ``close()`` removes it."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.on = False

    def start(self):
        import jax
        jax.profiler.start_trace(self.dir)
        self.on = True

    def stop(self):
        import jax
        if self.on:
            jax.profiler.stop_trace()
            self.on = False

    def load(self):
        from jax.profiler import ProfileData
        files = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if not files:
            raise FileNotFoundError(f"no xplane.pb under {self.dir}")
        return ProfileData.from_file(sorted(files)[-1])

    def close(self):
        self.stop()
        shutil.rmtree(self.dir, ignore_errors=True)


Interval = Tuple[int, int]


def short(name: str) -> str:
    """An operation's own name: its HLO text up to " = " (the rest lists
    its operands, which name other operations)."""
    return name.split(" = ", 1)[0].lstrip("%")


def _events(line, names=short) -> List[Tuple[str, int, int]]:
    return [(names(ev.name), int(ev.start_ns),
             int(ev.start_ns + ev.duration_ns)) for ev in line.events]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals: Iterable[Interval]) -> int:
    return sum(b - a for a, b in intervals)


class Reduced:
    """What the per-layer readers read from one trace."""

    def __init__(self, devices: Dict[str, Dict[str, list]],
                 host: List[Tuple[str, int, int]], t0: int, t1: int):
        self.devices = devices          # plane -> {"ops": [...], "modules"}
        self.host = host
        self.t0, self.t1 = t0, t1       # the traced window (ns)

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def busy_intervals(self, plane: str) -> List[Interval]:
        return union((a, b) for _, a, b in self.devices[plane]["ops"])

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        vals = [total(self.busy_intervals(p)) for p in self.devices]
        return sum(vals) / len(vals) * 1e-9 if vals else 0.0

    def op_seconds(self, pattern: str) -> float:
        """Device seconds of operations whose name contains ``pattern``,
        averaged over the devices."""
        vals = [sum(b - a for n, a, b in d["ops"] if pattern in n)
                for d in self.devices.values()]
        return sum(vals) / len(vals) * 1e-9 if vals else 0.0

    def op_count(self, pattern: str) -> int:
        return sum(1 for d in self.devices.values()
                   for n, _, _ in d["ops"] if pattern in n)

    def module_seconds(self, pattern: str) -> float:
        """Device seconds of programs whose name contains ``pattern``,
        averaged over the devices."""
        vals = [sum(b - a for n, a, b in d["modules"] if pattern in n)
                for d in self.devices.values()]
        return sum(vals) / len(vals) * 1e-9 if vals else 0.0

    def top_ops(self, n: int = 10) -> List[List]:
        """The operations with the most device time; control-flow ops,
        whose events span the operations of their bodies, are left out."""
        acc: Dict[str, int] = {}
        for d in self.devices.values():
            for name, a, b in d["ops"]:
                if name.startswith(("while", "conditional", "call")):
                    continue
                acc[name] = acc.get(name, 0) + (b - a)
        k = max(len(self.devices), 1)
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns / k * 1e-9] for name, ns in top]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """The longest device idle gaps, each named by the host span that
        covers most of it (``idle`` where none does)."""
        gaps: List[Tuple[int, int]] = []
        for p in self.devices:
            busy = self.busy_intervals(p)
            prev = self.t0
            for a, b in busy:
                if a > prev:
                    gaps.append((prev, a))
                prev = max(prev, b)
            if self.t1 > prev:
                gaps.append((prev, self.t1))
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for a, b in gaps[:n]:
            best, cover = "host idle", 0
            for name, ha, hb in self.host:
                c = min(b, hb) - max(a, ha)
                if c > cover:
                    best, cover = name, c
            out.append([best, (b - a) * 1e-9])
        return out


def reduce(pd, t0: Optional[int] = None, t1: Optional[int] = None,
           device_ids: Optional[Sequence[int]] = None) -> Reduced:
    """Reduce a ``ProfileData`` to ``Reduced``.  ``t0``/``t1`` (ns, the
    trace's clock) bound the window; by default the span of all device
    events.  ``device_ids`` picks the TPU planes (default: all)."""
    devices: Dict[str, Dict[str, list]] = {}
    host: List[Tuple[str, int, int]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            idx = int(plane.name.rsplit(":", 1)[1])
            if device_ids is not None and idx not in device_ids:
                continue
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE not in lines:
                continue
            devices[plane.name] = {
                "ops": _events(lines[OPS_LINE]),
                "modules": (_events(lines[MODULES_LINE], str)
                            if MODULES_LINE in lines else [])}
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend(e for e in _events(ln, str)
                            if e[0].startswith(HOST_PREFIX))
    stamps = [x for d in devices.values() for _, a, b in d["ops"]
              for x in (a, b)]
    lo = t0 if t0 is not None else (min(stamps) if stamps else 0)
    hi = t1 if t1 is not None else (max(stamps) if stamps else 0)

    def clip(evs):
        return [(n, max(a, lo), min(b, hi)) for n, a, b in evs
                if b > lo and a < hi]

    for d in devices.values():
        d["ops"] = clip(d["ops"])
        d["modules"] = clip(d["modules"])
    return Reduced(devices, clip(host), lo, hi)
