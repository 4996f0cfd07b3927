"""Device-resident request queue: preallocated job table + masked scatter.

The serving-side twin of the training pipeline's replay ring
(``repro.core.replay``): a fixed-capacity table of ``max_jobs`` job
slots lives on device as plain ``jnp`` arrays — the environment's
``trace`` (arrival/deadline/model/njl) and per-job ``state`` rows plus
queue bookkeeping (``occupied`` validity mask, host request ids,
cumulative SLA accumulators).  All operations are pure traceable
functions so the whole admit -> schedule -> retire tick compiles into
ONE device dispatch (``repro.core.serve.make_serving_tick``):

- :func:`queue_init`    allocate an empty queue for one env;
- :func:`queue_admit`   masked-scatter up to K packed admission rows
  into the lowest free slots (rows beyond the free count scatter to
  index ``capacity`` — out of bounds — and are *rejected*, reported via
  ``n_admitted`` so the host re-stages them next tick; same
  ``mode="drop"`` trick as ``replay_add_masked``);
- :func:`queue_retire`  drain completed jobs (done | missed): fold them
  into the cumulative global and per-tenant SLA accumulators, free
  their slots (arrival reset to ``INF`` makes them invisible to
  ``build_slots``/``mark_drops``), and emit a fixed-shape completion
  record for the host;
- :func:`queue_metrics` final metrics from the accumulators, computed
  with the same ops/dtypes as ``SchedulingEnv.metrics`` so a drained
  queue's numbers are bit-identical to an episode run with the full
  trace known upfront.

A queue for a re-entering registry (LM requests, ``SchedulingEnv.
reenters``, decided once from the registry) carries each job's output length ``n_out``, TPOT limit
``tpot`` and ``decode_start`` (``ds``) in the trace, its first-token
time beside its finish in the completion record, and TTFT and TPOT
attainment in the accumulators; its telemetry block counts decode
passes and first tokens.  A CNN queue has none of these leaves.

A freed slot's stale per-job state is harmless by construction: every
consumer of job rows gates on ``arrival <= t`` (INF for free slots) or
on the done/missed flags, and admission rewrites the full row.

Host-side staging (:func:`pack_admissions`) turns validated request
rows into the fixed ``(K,)``-shaped arrays the jitted tick consumes —
the only thing that crosses the host->device boundary per tick.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.sim.engine import INF
from repro.sim.env import SchedulingEnv
from repro.telemetry.metrics import counter_init, hist_init


def queue_telemetry_init(max_jobs: int) -> dict:
    """Device-resident telemetry block for one serving queue.

    Lives as a ``"tele"`` subdict inside the donated queue pytree —
    :func:`queue_admit` / :func:`queue_retire` pass it through
    untouched (``{**qs, ...}``), the tick updates it in-graph, and
    ``make_serving_flush`` surfaces it — so across-tick aggregates
    (queue-depth histogram, committed sub-jobs, tick count, the
    engine's loop iterations of this stream and the batched loop's
    trip count; a re-entering queue adds the decode passes committed and
    the first tokens) accumulate on device with zero extra host
    transfers.
    Depth-histogram edges sit at eighths of queue capacity.
    """
    edges = [max_jobs * f for f in
             (0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875)]
    return dict(depth_hist=hist_init(edges),
                committed=counter_init(),
                ticks=counter_init(),
                engine_iters=counter_init(),
                engine_trips=counter_init())


def queue_init(env: SchedulingEnv, telemetry: bool = False) -> dict:
    """Empty device queue for ``env`` (capacity = ``cfg.max_jobs``).

    The job table doubles as the env's episode ``trace``/``state``: free
    slots carry ``arrival = INF`` (never active, never overdue), so
    ``env.period`` runs on the queue unchanged.  ``telemetry=True``
    attaches the :func:`queue_telemetry_init` block (a structural
    change — the jitted tick re-traces, nothing else differs).
    """
    J = env.cfg.max_jobs
    trace = dict(
        arrival=jnp.full((J,), INF, jnp.float32),
        deadline=jnp.full((J,), INF, jnp.float32),
        q=jnp.ones((J,), jnp.float32),
        model=jnp.zeros((J,), jnp.int32),
        njl=jnp.zeros((J,), jnp.int32),
    )
    if env.reenters:
        trace.update(n_out=jnp.ones((J,), jnp.int32),
                     tpot=jnp.zeros((J,), jnp.float32),
                     ds=jnp.zeros((J,), jnp.int32))
    qs = dict(
        trace=trace,
        state=env.init_state(trace),
        occupied=jnp.zeros((J,), bool),
        rid=jnp.full((J,), -1, jnp.int32),
        acc=dict(
            admitted=jnp.zeros((), jnp.int32),
            rejected=jnp.zeros((), jnp.int32),
            counted=jnp.zeros((), jnp.int32),
            hits=jnp.zeros((), jnp.int32),
            ten_counted=jnp.zeros((env.num_models,), jnp.int32),
            ten_hit=jnp.zeros((env.num_models,), jnp.int32),
        ),
    )
    if env.reenters:
        qs["acc"].update(ttft_hits=jnp.zeros((), jnp.int32),
                         tpot_hits=jnp.zeros((), jnp.int32))
    if telemetry:
        qs["tele"] = queue_telemetry_init(J)
        if env.reenters:
            qs["tele"].update(passes=counter_init(),
                              first_tokens=counter_init())
    return qs


def admission_fields(env: SchedulingEnv) -> tuple[str, ...]:
    """The staged columns :func:`queue_admit` reads from ``adm`` besides
    ``valid``: a one-pass queue holds no output length or TPOT limit."""
    fields = ("model", "arrival", "deadline", "q", "rid")
    return fields + ("n_out", "tpot") if env.reenters else fields


def queue_admit(env: SchedulingEnv, qs: dict, adm: dict) -> tuple[dict, jnp.ndarray]:
    """Scatter packed admission rows into free slots (traceable).

    ``adm`` is the fixed-shape staging buffer from
    :func:`pack_admissions`: ``model``/``arrival``/``deadline``/``q``/
    ``rid``/``valid``, each ``(K,)``, valid rows packed first
    (``deadline`` travels explicitly rather than being recomputed as
    ``arrival + q`` on device: the trace generators compute it in
    float64 before the float32 cast, and re-deriving it in float32
    would break bit-parity with the host reference path).  The first
    ``min(n_valid, n_free)`` rows land in the lowest-index free slots
    in row order (a trace replayed in arrival order with an empty queue
    reproduces the static episode's slot assignment — the parity
    anchor); the rest scatter out of bounds and are dropped, counted in
    ``acc["rejected"]``.  A re-entering env's rows also carry ``n_out``
    and ``tpot``.  Returns ``(queue, n_admitted)``.
    """
    J = qs["occupied"].shape[0]
    K = adm["valid"].shape[0]
    free = ~qs["occupied"]
    # stable argsort of ~free: free slots first, each group in ascending
    # slot order — order[k] is the k-th lowest free slot index
    order = jnp.argsort(~free)
    k = jnp.arange(K)
    take = adm["valid"] & (k < jnp.sum(free))
    target = jnp.where(take, jnp.take(order, jnp.minimum(k, J - 1)), J)
    # dense one-hot writes instead of .at[target].set: XLA CPU lowers
    # batched scatters to serial per-element loops, which under the
    # stream vmap made admission ~13% of the whole tick; a (K, J) select
    # vectorizes (taken targets are distinct, so each slot gets at most
    # one row)
    hot = target[:, None] == jnp.arange(J)[None, :]          # (K, J)
    written = jnp.any(hot, axis=0)

    def put(arr, val):
        v = jnp.asarray(val).astype(arr.dtype)
        if arr.dtype == bool:
            upd = jnp.any(hot & v[:, None], axis=0)
        else:
            upd = jnp.sum(jnp.where(hot, v[:, None],
                                    jnp.zeros((), arr.dtype)), axis=0)
        return jnp.where(written, upd, arr)

    tr = qs["trace"]
    trace = dict(
        arrival=put(tr["arrival"], adm["arrival"]),
        deadline=put(tr["deadline"], adm["deadline"]),
        q=put(tr["q"], adm["q"]),
        model=put(tr["model"], adm["model"]),
        njl=put(tr["njl"], env.n_layers[adm["model"]]),
    )
    st = qs["state"]
    state = {**st,
             "nls": put(st["nls"], jnp.zeros((K,), jnp.int32)),
             "jready": put(st["jready"], adm["arrival"]),
             "missed": put(st["missed"], jnp.zeros((K,), bool)),
             "done": put(st["done"], jnp.zeros((K,), bool)),
             "hit": put(st["hit"], jnp.zeros((K,), bool)),
             "fjob": put(st["fjob"], jnp.full((K,), INF, jnp.float32))}
    if env.reenters:
        trace.update(n_out=put(tr["n_out"], adm["n_out"]),
                     tpot=put(tr["tpot"], adm["tpot"]),
                     ds=put(tr["ds"], env.decode_start[adm["model"]]))
        state.update(
            passes_left=put(st["passes_left"], adm["n_out"] - 1),
            t_first=put(st["t_first"], jnp.full((K,), INF, jnp.float32)),
            dl=put(st["dl"], adm["deadline"]))
    n_adm = jnp.sum(take).astype(jnp.int32)
    acc = {**qs["acc"],
           "admitted": qs["acc"]["admitted"] + n_adm,
           "rejected": qs["acc"]["rejected"]
           + jnp.sum(adm["valid"]).astype(jnp.int32) - n_adm}
    return {**qs, "trace": trace, "state": state,
            "occupied": put(qs["occupied"], jnp.ones((K,), bool)),
            "rid": put(qs["rid"], adm["rid"]), "acc": acc}, n_adm


def queue_retire(env: SchedulingEnv, qs: dict) -> tuple[dict, dict]:
    """Drain completed jobs into the accumulators and free their slots.

    Completed = occupied & (done | missed).  Emits a fixed-shape
    completion record (``completed`` mask over slots + the slot's
    ``rid``/``hit``/``missed``/``finish_us`` at retire time, and for a
    re-entering env ``t_first``/``passes_left``) — the only per-tick
    payload the host reads back.  A re-entering env also counts the
    completed jobs that met the TTFT limit (first token by the TTFT
    deadline) and the TPOT limit (done, the last token by the final
    deadline).
    """
    st, tr = qs["state"], qs["trace"]
    completed = qs["occupied"] & (st["done"] | st["missed"])
    hit = st["hit"] & completed
    mhot = tr["model"][:, None] == jnp.arange(env.num_models)[None, :]
    acc = {**qs["acc"],
           "counted": qs["acc"]["counted"]
           + jnp.sum(completed).astype(jnp.int32),
           "hits": qs["acc"]["hits"] + jnp.sum(hit).astype(jnp.int32),
           "ten_counted": qs["acc"]["ten_counted"]
           + jnp.sum(completed[:, None] & mhot, axis=0, dtype=jnp.int32),
           "ten_hit": qs["acc"]["ten_hit"]
           + jnp.sum(hit[:, None] & mhot, axis=0, dtype=jnp.int32)}
    out = dict(completed=completed, rid=qs["rid"], hit=st["hit"],
               missed=st["missed"], finish_us=st["fjob"],
               depth=jnp.sum(qs["occupied"]).astype(jnp.int32)
               - jnp.sum(completed).astype(jnp.int32))
    if env.reenters:
        ttft = completed & (st["t_first"] <= tr["deadline"])
        tpot = completed & st["done"] & (st["fjob"] <= st["dl"])
        acc.update(ttft_hits=acc["ttft_hits"] + jnp.sum(ttft, dtype=jnp.int32),
                   tpot_hits=acc["tpot_hits"] + jnp.sum(tpot, dtype=jnp.int32))
        out.update(t_first=st["t_first"], passes_left=st["passes_left"])
    trace = {**tr, "arrival": jnp.where(completed, INF, tr["arrival"])}
    return {**qs, "trace": trace,
            "occupied": qs["occupied"] & ~completed, "acc": acc}, out


def queue_metrics(qs: dict) -> dict:
    """Episode-style metrics from the cumulative accumulators.

    Same ops and dtypes as :meth:`SchedulingEnv.metrics` (int32 sums,
    float32 division), so a fully-drained queue reports bit-identical
    numbers to the host-loop reference on the same trace.  ``arrived``
    counts admissions (every real job of a fully-replayed trace).
    """
    acc = qs["acc"]
    out = dict(
        hits=acc["hits"], counted=acc["counted"], arrived=acc["admitted"],
        sla_rate=acc["hits"] / jnp.maximum(acc["counted"], 1),
        energy_uj=qs["state"]["energy"],
        rejected=acc["rejected"],
        ten_counted=acc["ten_counted"], ten_hit=acc["ten_hit"],
    )
    if "ttft_hits" in acc:
        # TTFT, TPOT and both-limits attainment (``sla_rate``)
        counted = jnp.maximum(acc["counted"], 1)
        out.update(ttft_hits=acc["ttft_hits"], tpot_hits=acc["tpot_hits"],
                   ttft_rate=acc["ttft_hits"] / counted,
                   tpot_rate=acc["tpot_hits"] / counted)
    return out


def pack_admissions(rows, tick_k: int) -> dict[str, np.ndarray]:
    """Host-side staging: pack validated request rows into the fixed
    ``(K,)`` admission buffer of one stream's tick.

    ``rows`` is a sequence of ``(rid, model_id, arrival_us, deadline_us,
    q_us)`` tuples, optionally with ``n_out`` and ``tpot_us`` after them
    (else 1 and 0: one pass), at most ``tick_k`` of them (the caller
    windows its backlog); the returned dict is the ``adm`` argument of
    :func:`queue_admit`, which reads ``n_out`` and ``tpot`` only where
    the tenants re-enter.
    """
    n = len(rows)
    if n > tick_k:
        raise ValueError(f"{n} admission rows > tick_k {tick_k}")
    adm = dict(model=np.zeros((tick_k,), np.int32),
               arrival=np.full((tick_k,), INF, np.float32),
               deadline=np.full((tick_k,), INF, np.float32),
               q=np.ones((tick_k,), np.float32),
               rid=np.full((tick_k,), -1, np.int32),
               valid=np.zeros((tick_k,), bool),
               n_out=np.ones((tick_k,), np.int32),
               tpot=np.zeros((tick_k,), np.float32))
    for i, (rid, mid, arr, dl, q, *lm) in enumerate(rows):
        adm["rid"][i] = rid
        adm["model"][i] = mid
        adm["arrival"][i] = arr
        adm["deadline"][i] = dl
        adm["q"][i] = q
        adm["valid"][i] = True
        if lm:
            adm["n_out"][i], adm["tpot"][i] = lm
    return adm
