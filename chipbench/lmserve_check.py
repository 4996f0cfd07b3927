"""Whether an LM serving tick's result is correct: ``reference_lm`` in its
place.

The same check as ``serve_check`` (the actor gap, and whether the float64
reference tick driven by the program's decisions leaves the program's
queue), on queues whose jobs re-enter: the reference tick is
``reference_lm``'s, and the compared queue holds each job's output
length, TPOT limit, ``decode_start``, passes left, first-token time and
current deadline, and the TTFT and TPOT counts. A hit decided by a time
within rounding of the limit it is compared with may count either way.
"""
from __future__ import annotations

import numpy as np

import reference as ref
import reference_lm as rl
import serve_check as chk

EXACT = chk.EXACT + ("n_out", "tpot", "ds", "passes_left", "ttft_hits",
                     "tpot_hits")
TIMES = chk.TIMES + ("t_first", "dl")

QUEUE_KEYS = {
    "trace": chk.QUEUE_KEYS["trace"] + ("n_out", "tpot", "ds"),
    "state": chk.QUEUE_KEYS["state"] + ("passes_left", "t_first", "dl"),
    "acc": chk.QUEUE_KEYS["acc"] + ("ttft_hits", "tpot_hits"),
}


def _near(a, b, t0):
    return np.abs(np.asarray(a) - np.asarray(b)) <= chk.time_tol(a, t0)


def match(prog: dict, r: dict, t0: float) -> bool:
    """The program's queue after a tick against the reference's."""
    for k in EXACT:
        a, b = np.asarray(prog[k]), np.asarray(r[k])
        if k == "arrival":
            both_inf = (a >= ref.INF / 2) & (b >= ref.INF / 2)
            if not np.all(both_inf | (a.astype(np.float64) == b)):
                return False
        elif not np.array_equal(a, b):
            return False
    for k in TIMES:
        if not np.all(chk._same_time(prog[k], r[k], t0)):
            return False
    off = np.asarray(prog["hit"]) != np.asarray(r["hit"])
    edge = (_near(r["fjob"], r["dl"], t0)
            | _near(r["t_first"], r["deadline"], t0))
    if np.any(off & ~edge):
        return False
    e_p, e_r = float(prog["energy"]), float(r["energy"])
    if abs(e_p - e_r) > 1e-5 * abs(e_r) + 1e-6:
        return False
    return prog["committed"] == r["committed"] or chk._committed_near(prog, r)


def reproduces(tb, q, s, n, a, prog) -> bool:
    """Whether the reference tick, deciding from the actor outputs ``a``,
    leaves the queue ``prog``."""
    prio, sa = ref.decide(np.asarray(a, np.float64))
    start, fin, en = ref.run_engine(tb, q, s, prio, sa)
    sets, near = chk._boundary_sets(tb, s, start, fin)
    t0 = float(q["t"])
    for c in sets:
        r = rl.finish_tick(tb, q, s, start, fin, en, sa, n, c)
        r["boundary"] = near
        if match(prog, r, t0):
            return True
    return False


def check_stream(tb: rl.Tables, params: dict, pre: dict, adm: dict,
                 prog: dict, a_prog, operands: str,
                 control: str | None = None) -> dict:
    """``serve_check.check_stream`` for a stream of LM requests, and the
    tick's slots that hold decode-pass rows (``decode_slots``)."""
    q, s, n = rl.prepare(tb, pre, adm)
    feats, mask = ref.features(tb, q, s)
    if control is not None:
        a_prog = ref.actor_apply(params, feats, mask, ref.ROUNDINGS[control])
    a_ref = ref.actor_apply(params, feats, mask, ref.ROUNDINGS[operands])
    a_exact = (a_ref if operands == "exact"
               else ref.actor_apply(params, feats, mask))
    decode = s["valid"] & (s["layer"] >= tb.ds[s["model"]])
    return dict(gap=chk.actor_gap(a_prog, a_ref, s["valid"]),
                decode_slots=int(np.sum(decode)),
                gap_exact=chk.actor_gap(a_prog, a_exact, s["valid"]),
                ok=control is not None or reproduces(tb, q, s, n, a_prog,
                                                     prog))


def flat_queue(qs: dict, s: int, out: dict | None = None) -> dict:
    """Stream ``s`` of the program's batched queue (and tick record) as
    one flat dict, LM fields included."""
    f = chk.flat_queue(qs, s, out)
    for group in ("trace", "state", "acc"):
        for k in QUEUE_KEYS[group][len(chk.QUEUE_KEYS[group]):]:
            x = np.asarray(qs[group][k])[s]
            f[k] = (x.astype(np.float64) if x.dtype.kind == "f"
                    else x.astype(np.int64) if x.ndim else int(x))
    return f
