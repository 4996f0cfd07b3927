"""Whether a serving tick's result is correct: the reference in its place.

For one stream and one tick the check takes the queue as the program held
it before the tick and the actor's outputs the program computed in it (its
decisions, as a served model's tokens are), and reads two things:

- the actor gap: the widest distance, over the valid slots, between the
  program's actor outputs and the plain float64 LSTM actor
  (``reference.actor_apply``) with the matmul operands rounded as the
  configuration states its precision;
- whether the float64 reference tick driven by the program's decisions
  (each slot's SA, the argmax of its utilities, and the priorities)
  leaves the program's queue: admission, drops, slots, the engine,
  commit and retire.

A sub-job whose start lies on the period boundary to the engine's
rounding may commit on either side; both are accepted.
"""
from __future__ import annotations

import itertools

import numpy as np

import reference as ref

CAP = 2.0            # the widest a gap between tanh outputs can be
BOUNDARY_US = 0.01   # a start this close to the boundary commits either way

EXACT = ("arrival", "deadline", "q", "model", "njl", "nls", "missed",
         "done", "occupied", "rid", "admitted", "rejected", "counted",
         "hits", "ten_counted", "ten_hit", "n_admitted", "depth",
         "completed", "t")
TIMES = ("jready", "fjob", "sa_free")


def time_tol(x, t0):
    """Tolerance of an absolute float32 time: the engine's float32
    rounding of times within the period, and the rounding of the
    absolute clock."""
    x = np.asarray(x, np.float64)
    return (1e-2 + 1e-4 * np.abs(x - t0)
            + 4 * np.spacing(np.abs(x).astype(np.float32)).astype(np.float64))


def _same_time(a, b, t0):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    big = (a >= ref.INF / 2) & (b >= ref.INF / 2)
    return big | (np.abs(a - b) <= time_tol(b, t0))


def match(prog: dict, r: dict, t0: float) -> bool:
    """The program's queue after a tick against the reference's."""
    for k in EXACT:
        if k == "arrival":
            a, b = np.asarray(prog[k], np.float64), np.asarray(r[k])
            ok = ((a >= ref.INF / 2) & (b >= ref.INF / 2)) | (a == b)
            if not np.all(ok):
                return False
        elif not np.array_equal(np.asarray(prog[k]), np.asarray(r[k])):
            return False
    for k in TIMES:
        if not np.all(_same_time(prog[k], r[k], t0)):
            return False
    # a finish within rounding of its deadline may count either way
    off = np.asarray(prog["hit"]) != np.asarray(r["hit"])
    if np.any(off & ~(np.abs(r["fjob"] - r["deadline"])
                      <= time_tol(r["fjob"], t0))):
        return False
    e_p, e_r = float(prog["energy"]), float(r["energy"])
    if abs(e_p - e_r) > 1e-5 * abs(e_r) + 1e-6:
        return False
    return prog["committed"] == r["committed"] or _committed_near(prog, r)


def _committed_near(prog, r) -> bool:
    return abs(int(prog["committed"]) - int(r["committed"])) <= r.get(
        "boundary", 0)


def _boundary_sets(tb, s, start, fin):
    """Commit sets: the start rule, and each flip of a start that lies on
    the period boundary to rounding."""
    base = s["valid"] & (start < tb.t_s - 1e-6) & (fin < ref.INF / 2)
    near = np.flatnonzero(s["valid"] & (np.abs(start - tb.t_s)
                                         <= BOUNDARY_US))
    sets = []
    for k in range(len(near) + 1):
        for flip in itertools.combinations(near, k):
            c = base.copy()
            c[list(flip)] = ~c[list(flip)]
            sets.append(c)
    return sets, len(near)


def actor_gap(a_prog, a_ref, valid) -> float:
    """Widest distance between two actors' outputs over the valid slots
    (:data:`CAP` where one is not a number)."""
    d = np.abs(np.asarray(a_prog, np.float64)[valid] - a_ref[valid])
    if d.size and not np.all(np.isfinite(d)):
        return CAP
    return float(d.max()) if d.size else 0.0


def reproduces(tb, q, s, n, a, prog) -> bool:
    """Whether the reference tick, deciding from the actor outputs ``a``,
    leaves the queue ``prog``."""
    prio, sa = ref.decide(np.asarray(a, np.float64))
    start, fin, en = ref.run_engine(tb, q, s, prio, sa)
    sets, near = _boundary_sets(tb, s, start, fin)
    t0 = float(q["t"])
    for c in sets:
        r = ref.finish_tick(tb, q, s, start, fin, en, sa, n, c)
        r["boundary"] = near
        if match(prog, r, t0):
            return True
    return False


def check_stream(tb: ref.Tables, params: dict, pre: dict, adm: dict,
                 prog: dict, a_prog, operands: str,
                 control: str | None = None) -> dict:
    """One stream's tick: ``pre``/``prog`` its queue before and after the
    tick as flat dicts (:func:`flat_queue`), ``adm`` the rows staged for
    it, ``a_prog`` the program's actor outputs.

    ``control`` (an operand rounding) puts the reference at that lower
    precision in the program's place: its outputs, and the queue its own
    decisions leave, which the reference tick reproduces by construction.
    Returns the actor gap against the reference at the stated precision
    (``gap``) and at float64 (``gap_exact``), and whether the reference
    tick reproduces the queue (``ok``)."""
    q, s, n = ref.prepare(tb, pre, adm)
    feats, mask = ref.features(tb, q, s)
    if control is not None:
        a_prog = ref.actor_apply(params, feats, mask, ref.ROUNDINGS[control])
    a_ref = ref.actor_apply(params, feats, mask, ref.ROUNDINGS[operands])
    a_exact = (a_ref if operands == "exact"
               else ref.actor_apply(params, feats, mask))
    return dict(gap=actor_gap(a_prog, a_ref, s["valid"]),
                gap_exact=actor_gap(a_prog, a_exact, s["valid"]),
                ok=control is not None or reproduces(tb, q, s, n, a_prog,
                                                     prog))


QUEUE_KEYS = {
    "trace": ("arrival", "deadline", "q", "model", "njl"),
    "state": ("nls", "jready", "missed", "done", "hit", "fjob", "sa_free",
              "t", "energy"),
    "acc": ("admitted", "rejected", "counted", "hits", "ten_counted",
            "ten_hit"),
}


def flat_queue(qs: dict, s: int, out: dict | None = None) -> dict:
    """Stream ``s`` of a host copy of the program's batched queue pytree
    (and of the tick's output record) as one flat dict of float64 /
    int64 / bool arrays, the layout ``reference.py`` uses."""
    def conv(x):
        x = np.asarray(x)[s]
        if x.dtype.kind == "f":
            return x.astype(np.float64) if x.ndim else float(x)
        if x.dtype.kind in "iu":
            return x.astype(np.int64) if x.ndim else int(x)
        return x
    f = {}
    for group, keys in QUEUE_KEYS.items():
        for k in keys:
            f[k] = conv(qs[group][k])
    f["occupied"] = conv(qs["occupied"])
    f["rid"] = conv(qs["rid"])
    if out is not None:
        f["completed"] = conv(out["completed"])
        f["depth"] = conv(out["depth"])
        f["n_admitted"] = conv(out["n_admitted"])
        f["committed"] = conv(out["committed"])
    return f


def flat_adm(adm: dict, s: int) -> dict:
    return {k: np.asarray(v)[s] for k, v in adm.items()}
