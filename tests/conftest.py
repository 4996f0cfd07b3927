"""Shared pytest config.

NOTE: no XLA_FLAGS here — smoke tests and benches must see ONE device;
only the subprocess tests of ``test_train_sharded.py`` force a
2-device CPU mesh, in their own environment.

The persistent compile cache is off for the whole suite: the entry
points that tests start as subprocesses (rl_train, serve) would turn it
on at the checkout's ``.jax_cache/``, and a test's result must not
depend on what an earlier run left there.  Set before any test module
builds its subprocess environment from ``os.environ``.
"""
import os

import pytest

os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"


def pytest_configure(config):
    config.addinivalue_line("markers",
                            "slow: long-running (subprocess drivers, e2e)")


def _serve_fieldwise(svc, request_streams, *, tick_k: int,
                     ticks: int | None = None, seed: int = 0) -> dict:
    """``svc.serve_stream`` as a plain loop that reads each field of a
    tick's ``out`` with its own ``np.asarray``, not the packed host
    record.  Returns what ``serve_stream`` returns, its ``stats`` less
    ``tick_wall_us`` and ``readbacks``."""
    import jax
    import numpy as np
    from repro.serving.queue import admission_fields
    from repro.serving.request import resolve_request
    from repro.serving.service import _tenant_table
    from repro.sim.engine import INF

    env, S = svc.env, len(request_streams)
    names = env.registry.model_names
    lm = env.reenters
    n_req = np.array([len(st) for st in request_streams])
    N = max(int(n_req.max()), 1)
    cols = dict(rid=np.full((S, N), -1, np.int32),
                model=np.zeros((S, N), np.int32),
                arrival=np.full((S, N), INF, np.float32),
                deadline=np.full((S, N), INF, np.float32),
                q=np.ones((S, N), np.float32),
                n_out=np.ones((S, N), np.int32),
                tpot=np.zeros((S, N), np.float32))
    for s, stream in enumerate(request_streams):
        for j, r in enumerate(sorted(stream, key=lambda r: r.arrival_us)):
            row = resolve_request(r, names)
            for k, v in (("rid", r.rid), ("model", row.model),
                         ("arrival", row.arrival_us),
                         ("deadline", row.deadline_us), ("q", row.q_us),
                         ("n_out", row.n_out), ("tpot", row.tpot_us)):
                cols[k][s, j] = v
    cols = {k: cols[k] for k in admission_fields(env)}
    n_ticks = env.cfg.periods if ticks is None else ticks
    tick, flush, queues = svc._tick_fns(S)
    keys = np.asarray(jax.random.split(jax.random.PRNGKey(seed), n_ticks))
    completions = [[] for _ in range(S)]

    def record(out, comp):
        rid, hit = np.asarray(out["rid"]), np.asarray(out["hit"])
        missed, fin = np.asarray(out["missed"]), np.asarray(out["finish_us"])
        if lm:
            t_first = np.asarray(out["t_first"])
            left = np.asarray(out["passes_left"])
        for s, j in zip(*np.nonzero(comp)):
            rec = dict(rid=int(rid[s, j]), hit=bool(hit[s, j]),
                       missed=bool(missed[s, j]), finish_us=float(fin[s, j]))
            if lm:
                rec.update(t_first_us=float(t_first[s, j]),
                           passes_left=int(left[s, j]))
            completions[s].append(rec)

    head = np.zeros((S,), np.int64)
    lane = np.arange(tick_k)
    admitted = deferred = depth_sum = 0
    for i in range(n_ticks):
        avail = (cols["arrival"] <= i * float(env.cfg.t_s_us)).sum(axis=1)
        n_stage = np.minimum(avail - head, tick_k)
        idx = np.minimum(head[:, None] + lane[None, :], N - 1)
        adm = {k: np.take_along_axis(v, idx, axis=1) for k, v in cols.items()}
        adm["valid"] = lane[None, :] < n_stage[:, None]
        queues, out = tick(svc.params, queues, adm, keys[i])
        n_adm = np.asarray(out["n_admitted"])
        comp = np.asarray(out["completed"])
        depth_sum += int(np.asarray(out["depth"]).sum())
        head += n_adm
        admitted += int(n_adm.sum())
        deferred += int((n_stage - n_adm).sum())
        if comp.any():
            record(out, comp)
    queues, fout = flush(queues)
    final = jax.tree.map(np.asarray, fout)
    record(final, final["completed"])

    keys_m = ("hits", "counted", "arrived", "sla_rate", "energy_uj")
    if lm:
        keys_m += ("ttft_hits", "tpot_hits", "ttft_rate", "tpot_rate")
    metrics = [dict({k: float(final[k][s]) for k in keys_m},
                    per_tenant=_tenant_table(names, final["ten_counted"][s],
                                             final["ten_hit"][s]))
               for s in range(S)]
    tot_c, tot_h = int(final["counted"].sum()), int(final["hits"].sum())
    aggregate = dict(sla_rate=tot_h / max(tot_c, 1), counted=tot_c,
                     hits=tot_h, arrived=int(final["arrived"].sum()),
                     energy_uj=float(final["energy_uj"].sum()),
                     completed=sum(len(c) for c in completions))
    if lm:
        for k in ("ttft", "tpot"):
            aggregate[k + "_rate"] = (int(final[k + "_hits"].sum())
                                      / max(tot_c, 1))
    stats = dict(streams=S, ticks=n_ticks, tick_k=tick_k, admitted=admitted,
                 deferred=deferred, unserved=int((n_req - head).sum()),
                 mean_depth=depth_sum / max(n_ticks, 1))
    return dict(metrics=metrics, aggregate=aggregate,
                completions=completions, stats=stats)


@pytest.fixture
def serve_fieldwise():
    """The field-by-field reference loop of the batched serving path."""
    return _serve_fieldwise


PARITY_KEYS = ("hits", "counted", "arrived", "sla_rate", "energy_uj")


def _assert_parity(ref: dict, m: dict):
    for k in PARITY_KEYS:
        assert ref[k] == m[k], f"{k}: host {ref[k]} != batched {m[k]}"
    assert ref["per_tenant"] == m["per_tenant"]


@pytest.fixture
def assert_parity():
    """Asserts that a host-loop episode (``serve_trace_host``) and one
    stream of ``serve_stream`` retired the same numbers, bit for bit."""
    return _assert_parity
