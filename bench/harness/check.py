"""The comparison that decides ``correct``.

Every served token of a sampled request is re-derived from the plain
reference (``reference.py``) by the paper's Alg. 1 with pseudorandom
acceptance, teacher-forced on the served text:

- the first token is the watermarked draw from the target's P at the
  prompt's last position; an accepted draft token (source flag 1) is the
  draft's watermarked draw from Q; the token after a run of a < K
  accepted drafts is the residual draw from (P - Q)_+, after K of them
  the bonus draw from P.  A position whose context hash was already used
  (repeated-context masking) draws from the raw distribution on the
  scheme's plain stream instead.  Gumbel draws are Gumbel-max races; a
  SynthID draw is the m-round tournament followed by a race.
- an accepted draft token needs its coin u < P/Q; the draft token that
  the residual replaced needs u >= P/Q.

Per position two gaps are read:

- the token gap: by how much the served token's race key, log mass plus
  the Gumbel noise of its keyed uniform, lies below the reference's best
  key at that position (nats; 0 when the reference picks the served
  token).  This is the widest-logit-gap check of greedy decoding, applied
  to the keyed race that makes a watermarked draw deterministic.  The
  served token's residual mass is taken as at least ``RESID_FLOOR`` of
  its P: where P and Q nearly cancel no reference in float32 can say
  which side of zero the residual lies.  The Gumbel noise is taken
  exactly: -log(u) of a uniform next to 1 is -log1p(u - 1), and u - 1 is
  exact in float32 there; a uniform of exactly 1 gives the key +inf.
  For the same reason a competing residual token keyed +inf by a uniform
  of 1 sets the best key only where its log(P/Q) is at least
  ``RATIO_MARGIN``.
- the accept gap: by how much log u lies on the wrong side of log(P/Q)
  for the served accept/reject decisions (nats).  The rejected draft
  token is the reference's own draft winner, and a rejection is read only
  where that winner leads the runner-up by ``DRAFT_MARGIN`` nats.

The numbers compared are the token gap's mean over the sampled tokens
(``token_gap_mean``) and the accept gap's mean over the decisions read
(``accept_gap_mean``).  The widest of each is read too but not compared:
on the chip it separates the program from the control by less than
three times (PERF.md), while the means, which count how often and how
far the served tokens and decisions leave the reference's, do.

The control (``fp8=True`` logits in place of the program) is read the
same way: at each position, the gap of the token, and of the decision,
that the control puts first.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from harness import prf, reference

RESID_FLOOR = 0.05
# nats: the program's log(P/Q) departs from the reference's by up to
# 0.073 at the accept decisions read on the chip (PERF.md)
RATIO_MARGIN = 0.25
DRAFT_MARGIN = 0.5
N_BUCKET = 64

FIRST, DRAFT, RESID, BONUS = 0, 1, 2, 3


def plan(tokens: np.ndarray, src: np.ndarray, prompt: np.ndarray,
         K: int, c: int) -> Dict[str, np.ndarray]:
    """Per served position: its kind (first token, accepted draft,
    residual or bonus), whether its context was already used, and its
    context hash."""
    n = len(tokens)
    text = np.concatenate([prompt, tokens]).astype(np.int64)
    ctx = prf.context_hashes(text, c)[len(prompt):]
    kind = np.zeros((n,), np.int32)
    seen = np.zeros((n,), bool)
    hist = {int(ctx[0])}
    j = 1
    while j < n:
        a = 0
        while j + a < n and src[j + a] == 1:
            a += 1
        end = min(j + a, n - 1)
        step = range(j, end + 1)
        for i in step:
            seen[i] = int(ctx[i]) in hist
            kind[i] = DRAFT if src[i] == 1 else (BONUS if a == K else RESID)
        for i in step:
            if not seen[i]:
                hist.add(int(ctx[i]))
        j = end + 1
    return {"kind": kind, "seen": seen, "ctx": ctx}


def _seeds(key: int, ctx: np.ndarray, kind: np.ndarray):
    """Per position: the watermark (g) seed, the finite-m draw seed and the
    plain-stream seed of the served kind's race, then the same three for
    the draft's race at that position."""
    ctx = jnp.asarray(ctx, jnp.uint32)
    kw = jnp.uint32(key)
    is_draft = jnp.asarray(kind == DRAFT)
    wm_stream = jnp.where(is_draft, prf.DRAFT, prf.TARGET).astype(jnp.uint32)
    plain = jnp.asarray(np.select([kind == DRAFT, kind == RESID,
                                   kind == BONUS],
                                  [prf.PLAIN + 1, prf.PLAIN + 2,
                                   prf.PLAIN + 3], prf.PLAIN + 3),
                        jnp.uint32)
    g = prf.seed(kw, wm_stream, ctx)
    draw = prf.seed(kw, prf.PLAIN + wm_stream, ctx)
    pl = prf.seed(kw, plain, ctx)
    dg = prf.seed(kw, prf.DRAFT, ctx)
    ddraw = prf.seed(kw, prf.PLAIN + prf.DRAFT, ctx)
    dpl = prf.seed(kw, prf.PLAIN + 1, ctx)
    return g, draw, pl, dg, ddraw, dpl


@partial(jax.jit, static_argnames=("m", "tournament"))
def _race_keys(dist, g_seed, draw_seed, plain_seed, seen, *, m, tournament):
    """(n, V) keys log(mass) + shift + Gumbel(u) of the keyed race over
    ``dist``, the shifts (for a tournament the log of the m rounds'
    factors less the log of the row's normalizer, else 0) and the race's
    uniforms.  Tournament schemes run the m rounds on the
    normalized row unless the context was seen."""
    n, V = dist.shape
    w = jnp.arange(V, dtype=jnp.uint32)
    if tournament:
        z = jnp.sum(dist, -1, keepdims=True)

        def body(i, c):
            pz, logf = c
            g = prf.gbit(g_seed[:, None], w[None] + jnp.uint32(V) * i)
            # a factor is 0 where all mass lies on g = 1; rounding can
            # take it just below 0, where the mass is still 0
            f = jnp.maximum(1.0 + g - jnp.sum(pz * g, -1, keepdims=True),
                            0.0)
            return pz * f, logf + jnp.log(f)

        _, logf = jax.lax.fori_loop(0, m, body,
                                    (dist / z, jnp.zeros_like(dist)))
        shift = jnp.where(seen[:, None], 0.0, logf - jnp.log(z))
        seed = jnp.where(seen, plain_seed, draw_seed)
    else:
        shift = jnp.zeros_like(dist)
        seed = jnp.where(seen, plain_seed, g_seed)
    u = prf.uniform(seed[:, None], w[None])
    return _keyed(dist, shift, u), shift, u


def _keyed(mass, shift, u):
    """Race keys log(mass) + shift + Gumbel(u).  -log(u) is exact: for u
    next to 1 it is log1p of the exact u - 1, and a uniform of exactly 1
    (drawn with probability 2^-24) gives the key +inf, so that token wins
    the race whatever its mass, as it does in the exponential race
    log(u) / mass.  A key of no mass, or of a shift of -inf, is -inf."""
    one = u >= 1.0
    e = jnp.where(u > 0.5, -jnp.log1p(jnp.where(one, 0.5, u) - 1.0),
                  -jnp.log(u))
    noise = jnp.where(one, jnp.inf, -jnp.log(jnp.where(one, 1.0, e)))
    dead = (mass <= 0) | jnp.isneginf(shift)
    return jnp.where(dead, -jnp.inf,
                     jnp.log(jnp.where(dead, 1.0, mass)) + shift + noise)


def _take(a, t):
    return jnp.take_along_axis(a, t[:, None], -1)[:, 0]


def _dists(p_logits, q_logits, kind, temperature):
    p = jax.nn.softmax(p_logits / temperature, -1)
    q = jax.nn.softmax(q_logits / temperature, -1)
    k = kind[:, None]
    dist = jnp.where(k == DRAFT, q,
                     jnp.where(k == RESID, jnp.maximum(p - q, 0.0), p))
    return p, q, dist


@partial(jax.jit, static_argnames=("m", "tournament", "temperature"))
def _gaps(p_logits, q_logits, tokens, kind, seen, u_acc, seeds,
          picked=None, *, m, tournament, temperature):
    """Per position: the token gap of ``tokens`` (or, given the control's
    ``picked`` logits, of the token they put first) and the accept gap of
    the decision at that slot."""
    g, draw, pl, dg, ddraw, dpl = seeds
    p, q, dist = _dists(p_logits, q_logits, kind, temperature)
    keys, shift, u = _race_keys(dist, g, draw, pl, seen, m=m,
                                tournament=tournament)
    dkeys, _, _ = _race_keys(q, dg, ddraw, dpl, seen, m=m,
                             tournament=tournament)
    if picked is not None:
        ps, qs, dist_s = _dists(picked[0], picked[1], kind, temperature)
        keys_s, _, _ = _race_keys(dist_s, g, draw, pl, seen, m=m,
                                  tournament=tournament)
        dkeys_s, _, _ = _race_keys(qs, dg, ddraw, dpl, seen, m=m,
                                   tournament=tournament)
        tokens = jnp.argmax(keys_s, -1).astype(jnp.int32)
        dtok = jnp.argmax(dkeys_s, -1).astype(jnp.int32)
    else:
        dtok = jnp.argmax(dkeys, -1).astype(jnp.int32)
    # a residual draw's mass is floored at RESID_FLOOR of its P
    mass = _take(dist, tokens)
    mass = jnp.where(kind == RESID,
                     jnp.maximum(mass, RESID_FLOOR * _take(p, tokens)), mass)
    k_tok = _keyed(mass, _take(shift, tokens), _take(u, tokens))
    # a uniform of exactly 1 keys a residual token +inf whatever its mass;
    # where its log(P/Q) is under RATIO_MARGIN, the reference cannot say
    # whether the program's residual was above 0, so the token does not
    # set the best key
    unsure = ((kind == RESID)[:, None] & (u >= 1.0) & (dist > 0)
              & (q > p * float(np.exp(-RATIO_MARGIN))))
    best = jnp.max(jnp.where(unsure, -jnp.inf, keys), -1)
    token_gap = jnp.where(k_tok >= best, 0.0, best - k_tok)
    token_gap = jnp.where(jnp.isnan(token_gap), jnp.inf, token_gap)
    top = jnp.argmax(keys, -1).astype(jnp.int32)
    b = jnp.argmax(jnp.where(unsure, -jnp.inf, keys), -1).astype(jnp.int32)
    diag = (b, k_tok, best, mass, _take(u, tokens), top, _take(p, top),
            _take(q, top), _take(dist, top), jnp.any(unsure, -1),
            _take(p, b), _take(q, b))

    # accepted drafts need u < P/Q; at a residual the draft winner that it
    # replaced needs u >= P/Q
    d = jnp.where(kind == DRAFT, tokens, dtok)
    log_u = jnp.where(u_acc > 0.5, jnp.log1p(jnp.minimum(u_acc, 1.0) - 1.0),
                      jnp.log(u_acc))
    ratio = jnp.log(_take(p, d)) - jnp.log(_take(q, d))
    if picked is not None:
        accept = log_u < jnp.log(_take(ps, d)) - jnp.log(_take(qs, d))
        read = (kind == DRAFT) | (kind == RESID)
    else:
        accept = kind == DRAFT
        top2 = jax.lax.top_k(dkeys, 2)[0]
        clear = top2[:, 0] - top2[:, 1] >= DRAFT_MARGIN
        read = (kind == DRAFT) | ((kind == RESID) & clear)
    wrong = jnp.where(accept, log_u - ratio, ratio - log_u)
    wrong = jnp.where(jnp.isnan(wrong), jnp.inf, wrong)
    accept_gap = jnp.where(read, jnp.maximum(wrong, 0.0), 0.0)
    return token_gap, accept_gap, tokens, diag, read


def request_gaps(params_t, params_d, mt: Dict[str, Any], md: Dict[str, Any],
                 prompt: np.ndarray, tokens: np.ndarray, src: np.ndarray,
                 key: int, spec: Dict[str, Any],
                 control: bool = False) -> Dict[str, np.ndarray]:
    """Per-position gaps of one served request against the reference; with
    ``control`` also the control's gaps at the same positions."""
    K, c = int(spec["K"]), int(spec["ctx_window"])
    n = len(tokens)
    n_pad = -(-n // N_BUCKET) * N_BUCKET
    pl = plan(tokens, src, prompt, K, c)
    text = np.concatenate([prompt, tokens[:-1]]).astype(np.int32)
    start = len(prompt) - 1

    def pad(a, fill=0):
        out = np.full((n_pad,), fill, a.dtype)
        out[:n] = a
        out[n:] = a[-1]
        return out

    kind, seen, ctx = pad(pl["kind"]), pad(pl["seen"]), pad(pl["ctx"])
    toks = jnp.asarray(pad(tokens.astype(np.int32)))
    seeds = _seeds(key, ctx, kind)
    u_acc = prf.uniform(prf.link(jnp.uint32(key), prf.ACCEPT),
                        jnp.asarray(ctx, jnp.uint32))
    static = dict(m=int(spec.get("m", 30)),
                  tournament=spec["watermark"] == "synthid",
                  temperature=float(spec["temperature"]))
    pt = reference.logits(params_t, mt, text, start, n, n_pad)
    pd = reference.logits(params_d, md, text, start, n, n_pad)
    ctl = None
    if control:
        ctl = (reference.logits(params_t, mt, text, start, n, n_pad,
                                fp8=True),
               reference.logits(params_d, md, text, start, n, n_pad,
                                fp8=True))
    out: Dict[str, Any] = {"kind": pl["kind"]}
    for pre, picked in (("", None), ("control_", ctl)):
        if pre and picked is None:
            continue
        parts = []
        # N_BUCKET rows at a time: the race keys are (rows, V) arrays
        for i in range(0, n_pad, N_BUCKET):
            sl = slice(i, i + N_BUCKET)
            parts.append(jax.device_get(_gaps(
                pt[sl], pd[sl], toks[sl], jnp.asarray(kind[sl]),
                jnp.asarray(seen[sl]), u_acc[sl],
                tuple(x[sl] for x in seeds),
                None if picked is None else (picked[0][sl], picked[1][sl]),
                **static)))
        tg = np.concatenate([p[0] for p in parts])[:n]
        out[pre + "token_gap"] = tg
        # the accept gaps of the decisions read
        read = np.concatenate([p[4] for p in parts])[:n]
        out[pre + "accept_gap"] = np.concatenate(
            [p[1] for p in parts])[:n][read]
        j = int(np.argmax(tg))
        b, k_tok, best, mass, u, bp, bq = (
            np.concatenate([p[3][k] for p in parts])[j]
            for k in (0, 1, 2, 3, 4, 10, 11))
        out[pre + "widest"] = {
            "pos": j, "kind": int(kind[j]), "seen": bool(seen[j]),
            "token": int(np.concatenate([p[2] for p in parts])[j]),
            "best_token": int(b), "key": float(k_tok),
            "best_key": float(best), "mass": float(mass),
            "u_minus_1": float(u) - 1.0, "best_p": float(bp),
            "best_q": float(bq)}
        # residual positions where a uniform of 1 did not set the best
        # key: that token, its P, Q and residual
        top, tp, tq, tr, uns = (np.concatenate([p[3][k] for p in parts])[:n]
                                for k in range(5, 10))
        out[pre + "unsure"] = [
            {"pos": int(i), "token": int(top[i]), "p": float(tp[i]),
             "q": float(tq[i]), "resid": float(tr[i])}
            for i in np.flatnonzero(uns)]
    return out


def sample(finished: List[int], lengths: Dict[int, int], seed: int,
           target_tokens: int, max_requests: int) -> List[int]:
    """Requests to check: the longest served one, then others in an order
    drawn from the seed, until ``target_tokens`` served tokens."""
    if not finished:
        return []
    rng = np.random.default_rng(seed + 17)
    longest = max(finished, key=lambda u: (lengths[u], -u))
    rest = [u for u in finished if u != longest]
    rng.shuffle(rest)
    out, total = [longest], lengths[longest]
    for u in rest:
        if total >= target_tokens or len(out) >= max_requests:
            break
        out.append(u)
        total += lengths[u]
    return out


def readings(per_request: List[Dict[str, np.ndarray]],
             control: bool = False) -> Dict[str, Optional[float]]:
    """The numbers compared: the mean token gap over every sampled token
    and the mean accept gap over every decision read; and, read but not
    compared, the widest of each, the number of decisions read, how many
    of them lie on the wrong side, and the five widest accept gaps.  With
    ``control`` the control's as well."""
    def widest(k):
        vals = [float(np.max(r[k])) for r in per_request if len(r[k])]
        return max(vals) if vals else None

    def mean(k):
        vals = np.concatenate([r[k] for r in per_request]) \
            if per_request else np.zeros((0,))
        return float(np.mean(vals)) if len(vals) else None

    out = {}
    for pre in ("", "control_") if control else ("",):
        out[pre + "token_gap_mean"] = mean(pre + "token_gap")
        out[pre + "accept_gap_mean"] = mean(pre + "accept_gap")
        out[pre + "token_gap_widest"] = widest(pre + "token_gap")
        out[pre + "accept_gap_widest"] = widest(pre + "accept_gap")
        acc = np.concatenate([r[pre + "accept_gap"] for r in per_request]) \
            if per_request else np.zeros((0,))
        out[pre + "decisions"] = int(len(acc))
        out[pre + "decisions_wrong"] = int(np.sum(acc > 0))
        out[pre + "accept_gap_top"] = [float(x) for x in
                                       np.sort(acc)[::-1][:5]]
        out[pre + "unsure"] = [x for r in per_request
                               for x in r.get(pre + "unsure", [])]
    if per_request:
        r = max(per_request, key=lambda r: float(np.max(r["token_gap"])))
        out["widest_at"] = r["widest"]
    return out
