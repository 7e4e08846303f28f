"""What one window did, reduced from the drive's records and the served
results: end-to-end metrics, and the live slot-steps and prefill chunks
that ran in it (for the operation and byte counts)."""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from harness.cell import Cell
from harness.serve import Window


def median(values: List[float]) -> Optional[float]:
    return float(np.median(values)) if values else None


def end_to_end(w: Window) -> Dict[str, Optional[float]]:
    """tokens/s visible in the window; the median TTFT over requests whose
    first token surfaced in it (from when each was sent or due); the
    median TPOT over requests completed in it.  A window holds tens of
    requests, too few for a 95th percentile that is not its maximum."""
    a, b = w.start, w.end
    tokens = sum(sum(1 for t in r.times if a < t <= b)
                 for r in w.reqs.values())
    ttft = [r.first - r.t0 for r in w.reqs.values()
            if r.first is not None and a < r.first <= b]
    tpot = [(r.last - r.first) / (r.n - 1) * 1e3 for r in w.reqs.values()
            if r.done and r.n >= 2 and a < r.last <= b]
    return {"tokens_per_s": tokens / (b - a) if b > a else None,
            "ttft_p50_s": median(ttft), "tpot_p50_ms": median(tpot),
            "n_ttft": len(ttft), "n_tpot": len(tpot)}


def steps_and_chunks(cell: Cell, w: Window, served: Dict[int, Dict],
                     a: float, b: float
                     ) -> Tuple[List[int], List[Tuple[int, int]]]:
    """The target cache length at each live slot-step whose tokens
    surfaced in (a, b], and the (start, n_valid) prefill chunks of the
    requests whose first token surfaced in it."""
    K = int(cell.spec["K"])
    ck = int(cell.serve["prefill_chunk"])
    steps: List[int] = []
    chunks: List[Tuple[int, int]] = []
    for uid, r in w.reqs.items():
        s = served.get(uid)
        if s is None or not r.times:
            continue
        S0 = len(r.prompt)
        src = s["src"]
        j, n = 1, len(src)
        while j < n:
            e = j
            while e < n and src[e] == 1 and e - j < K:
                e += 1
            if j < len(r.times) and a < r.times[j] <= b:
                steps.append(S0 + j - 1)
            j = e + 1
        if r.first is not None and a < r.first <= b:
            chunks.extend((i, min(ck, S0 - i)) for i in range(0, S0, ck))
    return steps, chunks
