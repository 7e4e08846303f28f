"""The drive: the program's ``Scheduler.run_stream`` over the paged pool,
fed by the cell's traffic, with every time taken on the benchmark's own
clock as tokens surface.

Closed loop: one client per slot; a client sends its next request the
moment its last one has all of its tokens, and a request is timed from
when it was sent.  Open loop: requests are due on the mix's arrival
schedule whether or not earlier ones finished, are submitted as soon as
the drive has control once they are due, and are timed from when they
were due.  ``run_stream`` gives control back only when tokens surface,
so a request due while no slot decodes waits for the next such round:
that wait counts in its time to first token.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from harness.cell import Cell
from harness.traffic import Traffic


def program_configs(cell: Cell):
    """The program's model and speculative configurations for a cell."""
    from repro.configs.base import ModelConfig
    from repro.serve import engine as E
    c = cell.config

    def model(m, name):
        return ModelConfig(
            name=name, arch_type="dense", n_layers=m["num_hidden_layers"],
            d_model=m["hidden_size"], n_heads=m["num_attention_heads"],
            n_kv_heads=m["num_key_value_heads"],
            d_ff=m["intermediate_size"], vocab=m["vocab_size"],
            head_dim=m["head_dim"], act="silu",
            rope_theta=float(m["rope_theta"]),
            rms_eps=float(m["rms_norm_eps"]), tie_embeddings=False)

    tcfg = model(c, cell.config_name)
    dcfg = model(c["draft"], cell.config_name + "-draft")
    s = cell.spec
    scfg = E.SpecConfig(K=int(s["K"]), ctx_window=int(s["ctx_window"]),
                        temperature=float(s["temperature"]),
                        watermark=s["watermark"], m=int(s.get("m", 30)),
                        accept="pseudorandom")
    return tcfg, dcfg, scfg


def build_scheduler(cell: Cell, t_params, d_params, key_word: int):
    from repro.serve.scheduler import Scheduler
    tcfg, dcfg, scfg = program_configs(cell)
    sv = cell.serve
    return Scheduler(
        t_params, d_params, tcfg, dcfg, scfg, batch=cell.batch,
        key=key_word, max_tokens=cell.output_max,
        max_prompt_len=cell.prompt_max, sync_every=int(sv["sync_every"]),
        page_size=int(sv["page_size"]), num_pages=cell.num_pages,
        prefill_chunk=int(sv["prefill_chunk"]),
        prefix_cache=bool(sv["prefix_cache"]),
        overlap=bool(sv["overlap"]))


def slots_busy(sched) -> bool:
    return bool(sched.queue) or any(s.phase != "FREE" for s in sched.slots)


@dataclasses.dataclass
class Req:
    uid: int
    prompt: np.ndarray
    n_tokens: int
    t0: float                      # sent (closed) or due (open)
    first: Optional[float] = None  # first token visible
    last: Optional[float] = None   # last token visible
    n: int = 0                     # tokens visible so far
    done: bool = False
    times: List[float] = dataclasses.field(default_factory=list)


class Compiles:
    """Counts the programs JAX lowers (each new executable, from the
    persistent cache or not) until ``close()``."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.n += 1

    def close(self):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


def warm_up(sched, cell: Cell, vocab: int, rng: np.random.Generator,
            compiles: Compiles, log: Callable[[str], None]) -> int:
    """Drive one full batch of short requests (two prefill chunks each, a
    few sync rounds of decode) until a pass compiles nothing new; returns
    the number of passes."""
    ck = int(cell.serve["prefill_chunk"])
    K1 = int(cell.spec["K"]) + 1
    n_tok = min(cell.output_max, 2 * int(cell.serve["sync_every"]) * K1)
    plen = min(cell.prompt_max, ck + 1)
    for p in range(1, 5):
        before = compiles.n
        for _ in range(cell.batch):
            sched.submit(rng.integers(1, vocab, size=plen).astype(np.int32),
                         n_tok)
        for _ in sched.run_stream():
            pass
        new = compiles.n - before
        log(f"  warm-up pass {p}: {new} programs lowered")
        if new == 0:
            return p
    raise RuntimeError("warm-up still compiles after 4 passes")


@dataclasses.dataclass
class Window:
    start: float
    end: float
    reqs: Dict[int, Req]
    compiles: int
    max_late_s: float                      # open loop: worst submit delay
    drained: bool
    error: Optional[str] = None


def drive(sched, cell: Cell, traffic: Traffic, seconds: float,
          compiles: Compiles, annotate=None, on_open=None, on_close=None,
          drain: bool = False, drain_s: float = 60.0) -> Window:
    """Offer the cell's traffic: ``ramp_s`` of traffic, then a window of
    ``seconds`` (``on_open``/``on_close`` run as it opens and closes, e.g.
    to start and stop a trace).  With ``drain`` what is in flight then
    finishes, for at most ``drain_s``, with no new requests; without, the
    drive stops at the window's close."""
    import contextlib
    span = annotate or (lambda name: contextlib.nullcontext())
    mix = cell.traffic
    closed = mix["loop"] == "closed"
    ramp = float(mix.get("ramp_s", 0.0))
    reqs: Dict[int, Req] = {}
    state = {"open": False, "closed": False, "compiles0": 0,
             "compiles1": None, "late": 0.0}
    t_start = time.perf_counter()
    w_open = t_start + ramp
    w_close = w_open + seconds
    pending: deque = deque()        # open loop: (due, prompt, n)
    finished: deque = deque()       # closed loop: clients ready to send
    next_due = [t_start]

    def submit(prompt, n, t0):
        with span("bench.submit"):
            uid = sched.submit(prompt, n)
        reqs[uid] = Req(uid=uid, prompt=prompt, n_tokens=n, t0=t0)

    def offering(now):
        return now < w_close

    def pump(now):
        """Open loop: submit every request that is due."""
        while offering(now):
            if not pending:
                prompt, n, gap = traffic.next()
                next_due[0] += gap
                pending.append((next_due[0], prompt, n))
            due, prompt, n = pending[0]
            if due > now:
                return
            pending.popleft()
            state["late"] = max(state["late"], now - due) \
                if state["open"] else state["late"]
            submit(prompt, n, due)

    def tick(now):
        if not state["open"] and now >= w_open:
            state["open"] = True
            state["compiles0"] = compiles.n
            if on_open is not None:
                on_open()
        if state["open"] and not state["closed"] and now >= w_close:
            state["closed"] = True
            state["compiles1"] = compiles.n
            if on_close is not None:
                on_close()
        if closed:
            # a client whose request has all its tokens sends the next
            while finished:
                finished.popleft()
                if offering(now):
                    prompt, n, _ = traffic.next()
                    submit(prompt, n, now)
        else:
            pump(now)

    # the clients' first requests
    if closed:
        for _ in range(cell.batch):
            prompt, n, _ = traffic.next()
            submit(prompt, n, t_start)
    else:
        pump(t_start)

    error = None
    try:
        while True:
            now = time.perf_counter()
            tick(now)
            if not slots_busy(sched):
                if not offering(now):
                    break
                # open loop, nothing in flight: wait for the next arrival
                with span("bench.wait_arrival"):
                    time.sleep(max(0.0, min(pending[0][0] if pending
                                            else now, w_close) - now))
                continue
            gen = sched.run_stream()
            while True:
                with span("bench.run_stream"):
                    ev = next(gen, None)
                if ev is None:
                    break
                now = time.perf_counter()
                uid, _tok, meta = ev
                r = reqs.get(uid)
                if r is not None:
                    if r.first is None:
                        r.first = now
                    r.last = now
                    r.n += 1
                    r.times.append(now)
                    if meta["final"]:
                        r.done = True
                        finished.append(uid)
                if now > w_close + drain_s:
                    raise RuntimeError(
                        f"requests still in flight {drain_s:.0f} s after "
                        "the window closed")
                tick(now)
                if not drain and now >= w_close:
                    break
            if not drain and time.perf_counter() >= w_close:
                break
    except Exception as e:          # the served path failed: not correct
        error = f"{type(e).__name__}: {e}"
    tick(max(time.perf_counter(), w_close))
    return Window(start=w_open, end=w_close, reqs=reqs,
                  compiles=state["compiles1"] - state["compiles0"],
                  max_late_s=state["late"], drained=drain and error is None,
                  error=error)


def served(sched, uid: int) -> Optional[Dict[str, Any]]:
    """The program's result for ``uid``: served tokens and their source
    flags (1 = accepted draft token)."""
    r = sched.results.get(uid)
    if r is None:
        return None
    return {"tokens": np.asarray(r.tokens), "src": np.asarray(r.src)}


def counters(sched) -> Dict[str, float]:
    """The scheduler's cumulative counts over flushed requests: accepted
    draft tokens, alive slot-steps and requests served."""
    s = sched.stats()
    alive = s["alive_slot_steps"]
    return {"accepted": s["aatps"] * alive, "alive": alive,
            "served": s["served"]}
