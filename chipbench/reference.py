"""Plain reference of one serving tick, in float64 NumPy.

Independent of the program: it imports nothing of ``repro`` and reads
only the configuration's tables, the weights the benchmark drew from the
seed, and a queue's state before a tick.  One stream's tick is

    admit     staged requests go FIFO into the lowest free slots
    drops     jobs past their deadline become misses
    slots     the uncommitted layers of active jobs, deadline order,
              packed into ``max_rq`` slots (a job's layers in a chain)
    features  the actor's input rows, a primer row of SA busy times first
    actor     LSTM -> FC -> ReLU -> FC -> tanh over the slots:
              priority and one utility per SA (argmax = the SA)
    engine    the float64 contention oracle (a copy of
              ``sim/engine.simulate_np``)
    commit    sub-jobs that start inside the period run to completion
    retire    finished and dropped jobs leave the queue and are counted

as the RELMAS paper (arXiv:2404.08950, Sec. 3-4) and the configuration's
``guarantees`` state them.  ``actor_apply`` takes an operand rounding:
the precision the configuration states, or the control's below it.
"""
from __future__ import annotations

import numpy as np

INF = 1e30
_EPS = 1e-5
BUSY_CAP = 4.0
TTD_NORM_PERIODS = 8.0


# --------------------------------------------------------------------------
# engine oracle: a copy of sim/engine.simulate_np
# --------------------------------------------------------------------------
def simulate_np(valid, assign, prio, cost, bw, dep, ready, sa_free, B):
    """Run the ready queue to completion. Returns (start, finish) float64.

    valid:  (n,) bool   slot holds a real SJ
    assign: (n,) int    SA index per SJ
    prio:   (n,) float  higher runs first (tie: lower slot index)
    cost:   (n,) float  contention-free execution time on assigned SA (us)
    bw:     (n,) float  bandwidth demand on assigned SA (GB/s)
    dep:    (n,) int    predecessor slot (-1 = none)
    ready:  (n,) float  earliest start time (us, external constraints)
    sa_free:(M,) float  time each SA becomes idle
    B:      float       shared DRAM bandwidth (GB/s)
    """
    valid = np.asarray(valid, bool)
    assign = np.asarray(assign, np.int64)
    prio = np.asarray(prio, np.float64)
    cost = np.asarray(cost, np.float64)
    bw = np.asarray(bw, np.float64)
    dep = np.asarray(dep, np.int64)
    ready = np.asarray(ready, np.float64)
    sa_free = np.asarray(sa_free, np.float64).copy()
    n, M = len(valid), len(sa_free)

    started = np.zeros(n, bool)
    finished = np.zeros(n, bool)
    progress = np.zeros(n)
    start = np.full(n, INF)
    finish = np.full(n, INF)
    t = 0.0

    def dep_ok():
        ok = dep < 0
        has = ~ok
        ok[has] = finished[dep[has]]
        return ok

    for _ in range(2 * n + M + 8):
        if not (valid & ~finished).any():
            break
        # ---- start phase: each idle SA admits its best ready candidate
        active = started & ~finished & valid
        for m in range(M):
            if t + _EPS < sa_free[m] or (active & (assign == m)).any():
                continue
            cand = valid & ~started & (assign == m) & dep_ok() & (ready <= t + _EPS)
            if cand.any():
                idxs = np.flatnonzero(cand)
                score = prio[idxs] - idxs * 1e-6
                i = idxs[np.argmax(score)]
                started[i] = True
                start[i] = t
                active[i] = True
        # ---- advance to next event
        next_t = INF
        if active.any():
            D = bw[active].sum()
            rho = min(1.0, B / D) if D > 0 else 1.0
            rem = (cost[active] - progress[active]) / max(rho, 1e-12)
            next_t = t + max(rem.min(), 0.0)
        else:
            rho = 1.0
        pend = valid & ~started & dep_ok()
        if pend.any():
            enab = np.maximum(sa_free[assign[pend]], ready[pend])
            enab = enab[enab > t + _EPS]
            if enab.size:
                next_t = min(next_t, enab.min())
        if next_t >= INF:
            break
        if active.any():
            progress[active] += (next_t - t) * rho
            done = active & (progress >= cost - _EPS)
            finish[done] = next_t
            finished |= done
        t = next_t
    return start, finish


# --------------------------------------------------------------------------
# actor
# --------------------------------------------------------------------------
def _sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def exact(x):
    return np.asarray(x, np.float64)


def actor_apply(params: dict, feats: np.ndarray, mask: np.ndarray,
                rnd=exact) -> np.ndarray:
    """feats (T, F) with the primer first, mask (T,) -> actions (T-1, G).

    ``rnd`` rounds every matmul operand (identity for the reference)."""
    p = {k: {n: exact(v) for n, v in d.items()} for k, d in params.items()}
    wx, wh, b = rnd(p["lstm"]["wx"]), rnd(p["lstm"]["wh"]), p["lstm"]["b"]
    H = wh.shape[0]
    h = np.zeros(H)
    c = np.zeros(H)
    hs = np.zeros((feats.shape[0], H))
    for t in range(feats.shape[0]):
        if mask[t]:
            g = rnd(feats[t]) @ wx + rnd(h) @ wh + b
            i, f, gg, o = g[:H], g[H:2 * H], g[2 * H:3 * H], g[3 * H:]
            c = _sigmoid(f) * c + _sigmoid(i) * np.tanh(gg)
            h = _sigmoid(o) * np.tanh(c)
        hs[t] = h
    z = np.maximum(rnd(hs) @ rnd(p["fc1"]["w"]) + p["fc1"]["b"], 0.0)
    a = np.tanh(rnd(z) @ rnd(p["fc2"]["w"]) + p["fc2"]["b"])
    return a[1:]


# --------------------------------------------------------------------------
# one stream's tick
# --------------------------------------------------------------------------
class Tables:
    """The configuration's cost tables, as float64 arrays."""

    def __init__(self, cfg: dict):
        t = cfg["tables"]
        self.cfg = cfg
        self.lat = np.asarray(t["lat_us"], np.float32).astype(np.float64)
        self.bw = np.asarray(t["bw_gbps"], np.float32).astype(np.float64)
        self.en = np.asarray(t["en_uj"], np.float32).astype(np.float64)
        self.n_layers = np.asarray(t["n_layers"], np.int64)
        self.M = int(t["num_sas"])
        self.lmax = int(t["lmax"])
        self.num_models = len(t["n_layers"])
        self.t_s = float(cfg["t_s_us"])
        self.R = int(cfg["max_rq"])
        self.B = float(cfg["bandwidth_gbps"])


def admit(tb: Tables, q: dict, adm: dict) -> tuple[dict, int]:
    """Staged rows (valid ones first) into the lowest free slots."""
    q = {k: (v.copy() if isinstance(v, np.ndarray) else v)
         for k, v in q.items()}
    free = np.flatnonzero(~q["occupied"])
    rows = np.flatnonzero(adm["valid"])
    n = min(len(rows), len(free))
    for r, j in zip(rows[:n], free[:n]):
        q["arrival"][j] = adm["arrival"][r]
        q["deadline"][j] = adm["deadline"][r]
        q["q"][j] = adm["q"][r]
        q["model"][j] = adm["model"][r]
        q["njl"][j] = tb.n_layers[adm["model"][r]]
        q["nls"][j] = 0
        q["jready"][j] = adm["arrival"][r]
        q["missed"][j] = q["done"][j] = q["hit"][j] = False
        q["fjob"][j] = INF
        q["occupied"][j] = True
        q["rid"][j] = adm["rid"][r]
    q["admitted"] += n
    q["rejected"] += len(rows) - n
    return q, n


def drops(q: dict, now: float) -> None:
    overdue = ((q["arrival"] <= now) & ~q["done"] & ~q["missed"]
               & (q["deadline"] < now))
    q["missed"] = q["missed"] | overdue


def slots(tb: Tables, q: dict) -> dict:
    """Deadline-ordered ready queue of ``R`` slots at the clock ``t``."""
    t = q["t"]
    active = (q["arrival"] <= t) & ~q["done"] & ~q["missed"]
    rem = np.where(active, q["njl"] - q["nls"], 0)
    key = np.where(active & (rem > 0), q["deadline"], INF)
    order = np.argsort(key, kind="stable")
    R = tb.R
    job = np.zeros(R, np.int64)
    layer = np.zeros(R, np.int64)
    valid = np.zeros(R, bool)
    i = 0
    for j in order:
        for k in range(int(rem[j])):
            if i >= R:
                break
            job[i], layer[i], valid[i] = j, q["nls"][j] + k, True
            i += 1
    layer = np.clip(layer, 0, tb.lmax - 1)
    dep = np.full(R, -1, np.int64)
    same = valid[1:] & valid[:-1] & (job[1:] == job[:-1])
    dep[1:][same] = np.arange(R - 1)[same]
    model = q["model"][job]
    ready = np.where(dep < 0, np.maximum(0.0, q["jready"][job] - t), 0.0)
    v = valid[:, None]
    return dict(job=job, layer=layer, valid=valid, dep=dep,
                ready=np.where(valid, ready, 0.0),
                cost_all=np.where(v, tb.lat[model, layer], 0.0),
                bw_all=np.where(v, tb.bw[model, layer], 0.0),
                en_all=np.where(v, tb.en[model, layer], 0.0),
                model=model, deadline=q["deadline"][job],
                arrival=q["arrival"][job])


def features(tb: Tables, q: dict, s: dict) -> tuple[np.ndarray, np.ndarray]:
    """Actor input (R+1, 4+2M) with the primer row first, and its mask."""
    t, ts = q["t"], tb.t_s
    tsn = ts * TTD_NORM_PERIODS
    v = s["valid"].astype(np.float64)[:, None]
    cols = [((s["model"] + 1.0) / tb.num_models)[:, None],
            ((s["layer"] + 1.0) / tb.lmax)[:, None],
            np.clip((s["deadline"] - t) / tsn, -1.0, 1.0)[:, None],
            np.clip((t - s["arrival"]) / tsn, 0.0, 1.0)[:, None],
            np.clip(s["cost_all"] / ts, 0.0, 2.0) / 2.0,
            s["bw_all"] / tb.B]
    rows = np.concatenate([c * v for c in cols], axis=1)
    busy = np.maximum(0.0, q["sa_free"] - t) / ts
    primer = np.concatenate([np.zeros(4), np.clip(busy, 0.0, BUSY_CAP)
                             / BUSY_CAP, np.zeros(tb.M)])
    return (np.concatenate([primer[None], rows]),
            np.concatenate([[True], s["valid"]]))


def run_engine(tb: Tables, q: dict, s: dict, prio, sa):
    i = np.arange(tb.R)
    cost = s["cost_all"][i, sa]
    bw = s["bw_all"][i, sa]
    sa_free = np.maximum(0.0, q["sa_free"] - q["t"])
    start, fin = simulate_np(s["valid"], sa, prio, cost, bw, s["dep"],
                             s["ready"], sa_free, tb.B)
    return start, fin, s["en_all"][i, sa]


def commit(tb: Tables, q: dict, s: dict, start, fin, en, sa,
           committed=None) -> dict:
    """Sub-jobs that start inside the period run to completion; the clock
    moves on one period.  ``committed`` overrides the start rule (for a
    start that lies on the period boundary to rounding)."""
    t, ts = q["t"], tb.t_s
    if committed is None:
        committed = s["valid"] & (start < ts - 1e-6) & (fin < INF / 2)
    q = dict(q)
    J = len(q["nls"])
    ncom = np.zeros(J, np.int64)
    jlast = np.full(J, -INF)
    for i in np.flatnonzero(committed):
        ncom[s["job"][i]] += 1
        jlast[s["job"][i]] = max(jlast[s["job"][i]], fin[i])
    nls = q["nls"] + ncom
    jready = np.where(ncom > 0, t + jlast, q["jready"])
    arrived = q["arrival"] <= t
    newly = (arrived & ~q["done"] & ~q["missed"] & (nls >= q["njl"])
             & (ncom > 0))
    q["fjob"] = np.where(newly, jready, q["fjob"])
    q["hit"] = q["hit"] | (newly & (q["fjob"] <= q["deadline"]))
    q["done"] = q["done"] | newly
    q["nls"], q["jready"] = nls, jready
    q["energy"] = q["energy"] + float(np.sum(np.where(committed, en, 0.0)))
    sa_free = q["sa_free"].copy()
    for m in range(tb.M):
        f = fin[committed & (sa == m)]
        if f.size:
            sa_free[m] = max(sa_free[m], t + f.max())
    q["sa_free"] = sa_free
    q["committed"] = int(np.sum(s["valid"] & (start < ts)))
    q["t"] = t + ts
    return q


def retire(tb: Tables, q: dict) -> dict:
    done = q["occupied"] & (q["done"] | q["missed"])
    q = dict(q)
    q["completed"] = done
    q["counted"] += int(done.sum())
    q["hits"] += int((q["hit"] & done).sum())
    tc, th = q["ten_counted"].copy(), q["ten_hit"].copy()
    for j in np.flatnonzero(done):
        tc[q["model"][j]] += 1
        th[q["model"][j]] += int(q["hit"][j])
    q["ten_counted"], q["ten_hit"] = tc, th
    q["depth"] = int(q["occupied"].sum()) - int(done.sum())
    q["arrival"] = np.where(done, INF, q["arrival"])
    q["occupied"] = q["occupied"] & ~done
    return q


def decide(a: np.ndarray):
    """Priorities and SAs from the actor's actions."""
    return a[:, 0].copy(), np.argmax(a[:, 1:], axis=-1)


def prepare(tb: Tables, pre: dict, adm: dict):
    """Admit and drop: the queue the period schedules, and its slots."""
    q, n = admit(tb, pre, adm)
    drops(q, q["t"])
    s = slots(tb, q)
    return q, s, n


def finish_tick(tb: Tables, q: dict, s: dict, start, fin, en, sa, n_adm,
                committed=None) -> dict:
    q = commit(tb, q, s, start, fin, en, sa, committed)
    q = retire(tb, q)
    q["n_admitted"] = n_adm
    return q


def _rounding(dtype_name: str):
    def rnd(x):
        import ml_dtypes
        dt = getattr(ml_dtypes, dtype_name)
        return np.asarray(x, np.float64).astype(dt).astype(np.float64)
    return rnd


# matmul operand roundings, by the name a configuration states them
# under: "bf16" the operands of a one-pass bfloat16 matmul (the TPU's
# default for float32), "fp8" those of a float8 e4m3 matmul
ROUNDINGS = {"exact": exact, "bf16": _rounding("bfloat16"),
             "fp8": _rounding("float8_e4m3fn")}
