"""Plain reference of whole LM requests: their layer costs and one tick.

Independent of the program: it imports nothing of ``repro`` and reads
only the configuration (the catalog's config keys and the tables), the
weights the benchmark drew from the seed, and a queue's state before a
tick. It reuses ``reference.py`` for what LM requests share with the
paper's CNN jobs: the float64 engine oracle, the actor and its input
rows, and the admission of rows into free slots.

A request asks for ``n_out`` tokens: one prefill pass over its prompt
(the first token), then ``n_out - 1`` decode passes, each one token
against a cache of the class's fixed context. Its table is one chain,
the prefill rows then the decode-pass rows (from ``decode_start``).
One stream's tick is

    admit     staged requests go FIFO into the lowest free slots, with
              their output length, TPOT limit and ``decode_start``
    drops     jobs past their current deadline become misses: the TTFT
              deadline before the first token, the final deadline
              ``t_first + tpot * (n_out - 1)`` after it
    slots     the uncommitted layers of each active job's current pass
              only, packed into ``max_rq`` slots in the order of the
              deadline of the token the pass yields: the TTFT deadline,
              then ``t_first + tpot * k`` for the ``k + 1``-th token
    engine    the float64 contention oracle over the program's decisions
    commit    sub-jobs that start inside the period run to completion;
              a job whose pass ends goes back to ``decode_start`` while
              it has passes left, and is done after its last; the end of
              the prefill pass is the first token
    retire    finished and dropped jobs leave the queue; a job hits iff
              its first token met the TTFT deadline and its last token
              the final deadline, and each limit is counted on its own

as DistServe (arXiv:2401.09670) judges a request and the configuration's
``guarantees`` state it.
"""
from __future__ import annotations

import numpy as np

import reference as ref

INF = ref.INF
BYTES = 2          # bfloat16 weights, activations and cache


# --------------------------------------------------------------------------
# layer costs from the catalog's config keys
# --------------------------------------------------------------------------
def _ffn(c: dict, layer: int, S: int) -> tuple[int, int]:
    """(MACs, weight elements streamed) of one layer's FFN for S tokens."""
    d = c["hidden_size"]
    dense = c["first_k_dense_replace"]
    if layer < dense or (layer - dense) % c["moe_layer_freq"]:
        return 3 * S * d * c["intermediate_size"], 3 * d * c["intermediate_size"]
    width, E = c["moe_intermediate_size"], c["n_routed_experts"]
    shared, top = c["n_shared_experts"], c["num_experts_per_tok"]
    router = d * E
    macs = S * (3 * d * width * (shared + top) + router)
    # a routed expert streams in once when any of the S tokens picks it
    return macs, 3 * d * width * (shared + min(E, S * top)) + router


def _mla_layer(c: dict, layer: int, S: int, ctx: int, decode: bool) -> dict:
    """One MLA + FFN layer: MACs and bytes in, of weights and out."""
    if c["q_lora_rank"] is not None:
        raise ValueError("the reference costs MLA without q compression")
    d, H = c["hidden_size"], c["num_attention_heads"]
    nope, rope = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    vd, rank = c["v_head_dim"], c["kv_lora_rank"]
    q = d * H * (nope + rope)                 # q_proj
    kv_a = d * (rank + rope)                  # kv_a_proj_with_mqa
    kv_b = rank * H * (nope + vd)             # kv_b_proj
    o = H * vd * d                            # o_proj
    if decode:
        # absorbed: q_nope through kv_b's key half, scores over the
        # cached latent and rope key, values over the latent, then
        # kv_b's value half
        attn = S * H * (nope * rank + ctx * (rank + rope) + ctx * rank
                        + rank * vd)
        cache_read = ctx * (rank + rope) * BYTES
    else:
        # the latent up-projected for every token, then each token
        # attends over all ctx tokens
        attn = S * kv_b + S * ctx * H * (nope + rope) + S * ctx * H * vd
        cache_read = 0
    ffn, w_ffn = _ffn(c, layer, S)
    return dict(macs=S * (q + kv_a + o) + attn + ffn,
                w_bytes=(q + kv_a + kv_b + o + w_ffn) * BYTES,
                in_bytes=S * d * BYTES + cache_read,
                out_bytes=S * d * BYTES + S * (rank + rope) * BYTES)


def _pass(c: dict, S: int, ctx: int, decode: bool) -> list[dict]:
    d, V = c["hidden_size"], c["vocab_size"]
    embed = dict(macs=S * d, w_bytes=0, in_bytes=S * d * BYTES,
                 out_bytes=S * d * BYTES)
    head = dict(macs=S * d * V, w_bytes=d * V * BYTES,
                in_bytes=S * d * BYTES, out_bytes=S * V * BYTES)
    return ([embed] + [_mla_layer(c, i, S, ctx, decode)
                       for i in range(c["num_hidden_layers"])] + [head])


def request_rows(c: dict, prompt: int, ctx: int) -> tuple[list[dict], int]:
    """A whole request's rows (prefill over ``prompt`` tokens, then one
    decode pass against a ``ctx``-token cache) and where the decode pass
    starts."""
    pre = _pass(c, prompt, prompt, decode=False)
    return pre + _pass(c, 1, ctx, decode=True), len(pre)


# --------------------------------------------------------------------------
# one stream's tick
# --------------------------------------------------------------------------
class Tables(ref.Tables):
    """The configuration's cost tables, with each tenant's
    ``decode_start``."""

    def __init__(self, cfg: dict):
        super().__init__(cfg)
        self.ds = np.asarray(cfg["tables"]["decode_start"], np.int64)


def admit(tb: Tables, q: dict, adm: dict) -> tuple[dict, int]:
    """Staged rows into the lowest free slots, each with its passes."""
    free = np.flatnonzero(~q["occupied"])
    rows = np.flatnonzero(adm["valid"])[:len(free)]
    q, n = ref.admit(tb, q, adm)
    for r, j in zip(rows, free):
        q["n_out"][j] = adm["n_out"][r]
        q["tpot"][j] = adm["tpot"][r]
        q["ds"][j] = tb.ds[adm["model"][r]]
        q["passes_left"][j] = adm["n_out"][r] - 1
        q["t_first"][j] = INF
        q["dl"][j] = adm["deadline"][r]
    return q, n


def drops(q: dict, now: float) -> None:
    overdue = ((q["arrival"] <= now) & ~q["done"] & ~q["missed"]
               & (q["dl"] < now))
    q["missed"] = q["missed"] | overdue


def pass_end(q: dict) -> np.ndarray:
    return np.where(q["nls"] < q["ds"], q["ds"], q["njl"])


def token_deadline(q: dict) -> np.ndarray:
    """The deadline of the token each job's current pass yields."""
    tokens = q["n_out"] - 1 - q["passes_left"]     # after the first
    return np.where(q["t_first"] < INF / 2,
                    q["t_first"] + q["tpot"] * tokens, q["dl"])


def slots(tb: Tables, q: dict) -> dict:
    """The current pass of each active job, by the deadline of the token
    it yields, packed into ``R`` slots (a pass's layers in a chain)."""
    t = q["t"]
    active = (q["arrival"] <= t) & ~q["done"] & ~q["missed"]
    rem = np.where(active, pass_end(q) - q["nls"], 0)
    due = token_deadline(q)
    key = np.where(active & (rem > 0), due, INF)
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
                model=model, deadline=due[job],
                arrival=q["arrival"][job])


def commit(tb: Tables, q: dict, s: dict, start, fin, en, sa,
           committed=None) -> dict:
    """Commit the period, end passes, re-enter; the clock moves on one
    period. ``committed`` overrides the start rule (for a start that
    lies on the period boundary to rounding)."""
    t, ts = q["t"], tb.t_s
    if committed is None:
        committed = s["valid"] & (start < ts - 1e-6) & (fin < INF / 2)
    q = {k: (v.copy() if isinstance(v, np.ndarray) else v)
         for k, v in q.items()}
    J = len(q["nls"])
    ncom = np.zeros(J, np.int64)
    jlast = np.full(J, -INF)
    for i in np.flatnonzero(committed):
        ncom[s["job"][i]] += 1
        jlast[s["job"][i]] = max(jlast[s["job"][i]], fin[i])
    end = pass_end(q)
    for j in np.flatnonzero(ncom):
        q["nls"][j] += ncom[j]
        q["jready"][j] = t + jlast[j]
        live = q["arrival"][j] <= t and not (q["done"][j] or q["missed"][j])
        if not live or q["nls"][j] < end[j]:
            continue
        if end[j] == q["ds"][j]:               # the prefill: first token
            q["t_first"][j] = q["jready"][j]
            q["dl"][j] = q["t_first"][j] + q["tpot"][j] * (q["n_out"][j] - 1)
        if q["passes_left"][j] > 0:            # re-enter for the next token
            q["passes_left"][j] -= 1
            q["nls"][j] = q["ds"][j]
        else:
            q["done"][j] = True
            q["fjob"][j] = q["jready"][j]
            q["hit"][j] = (q["t_first"][j] <= q["deadline"][j]
                           and q["fjob"][j] <= q["dl"][j])
    q["energy"] = q["energy"] + float(np.sum(np.where(committed, en, 0.0)))
    for m in range(tb.M):
        f = fin[committed & (sa == m)]
        if f.size:
            q["sa_free"][m] = max(q["sa_free"][m], t + f.max())
    q["committed"] = int(np.sum(s["valid"] & (start < ts)))
    q["t"] = t + ts
    return q


def retire(tb: Tables, q: dict) -> dict:
    """Drain finished and dropped jobs; count both limits and each."""
    done = q["occupied"] & (q["done"] | q["missed"])
    q = ref.retire(tb, q)
    q["ttft_hits"] += int(np.sum(done & (q["t_first"] <= q["deadline"])))
    q["tpot_hits"] += int(np.sum(done & q["done"] & (q["fjob"] <= q["dl"])))
    return q


def prepare(tb: Tables, pre: dict, adm: dict):
    """Admit and drop: the queue the period schedules, and its slots."""
    q, n = admit(tb, pre, adm)
    drops(q, q["t"])
    return q, slots(tb, q), n


def finish_tick(tb: Tables, q: dict, s: dict, start, fin, en, sa, n_adm,
                committed=None) -> dict:
    q = retire(tb, commit(tb, q, s, start, fin, en, sa, committed))
    q["n_admitted"] = n_adm
    return q
