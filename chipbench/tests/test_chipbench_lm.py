"""The LM serving cell at a size a CPU test run holds: the layer costs
against the plain function of the catalog's keys, the copied load
generator against the program's, every tick of a session against
``reference_lm``, and the cell's check on the program, its control and
each planted fault.

The small cell keeps the configuration's tables and widths and cuts the
streams (4), the queue (``max_jobs`` 8, ``max_rq`` 32), the session (400
ticks, 800 where requests have to finish) and the output lengths
(median 8, at most 16). On the CPU the program's float32 matmuls are
exact, so the configuration states that precision here (``operands``
exact)."""
import time

import numpy as np
import pytest

import faults
import lmserve_check as lchk
import lmserve_cell
import loadgen_lm
import reference_lm as rl
import run
import serve_cell
import serve_check as chk

CELL = "serve-dsv2lite-chat"
SEED = 2**31 + 77
SEEDS = [[2**31 + 12345, 0], [2**31 + 12345, 3], [7, 1]]
ARCH_KEYS = {"n_layers": "num_hidden_layers", "d_model": "hidden_size",
             "n_heads": "num_attention_heads",
             "n_kv": "num_key_value_heads", "d_ff": "intermediate_size",
             "vocab": "vocab_size", "n_experts": "n_routed_experts",
             "top_k": "num_experts_per_tok",
             "moe_d_ff": "moe_intermediate_size",
             "n_shared_experts": "n_shared_experts",
             "first_k_dense": "first_k_dense_replace",
             "moe_every": "moe_layer_freq", "kv_lora_rank": "kv_lora_rank",
             "qk_nope_head_dim": "qk_nope_head_dim",
             "qk_rope_head_dim": "qk_rope_head_dim",
             "v_head_dim": "v_head_dim"}


def _small():
    bench, c, cfg, traffic = run.cell_spec(CELL)
    return bench, c, dict(cfg, streams=4, max_jobs=8, max_rq=32,
                          operands="exact"), dict(
        traffic, session_ticks=400, rate_scale=4.0, out_median=8,
        out_min=2, out_max=16)


def _run():
    bench, c, cfg, traffic = _small()
    return run.run_cell(bench, c, cfg, traffic, SEED, 0.0, 0,
                        time.perf_counter())


# ---------------------------------------------------------------------------
# the configuration against the catalog and the program
# ---------------------------------------------------------------------------
def test_arch_config_is_the_catalogs():
    from repro.configs.registry import TENANT_ARCHS
    cfg = run.cell_spec(CELL)[2]
    arch = TENANT_ARCHS[cfg["classes"][cfg["tenants"][0]]["arch"]]
    assert cfg["q_lora_rank"] is None
    for field, key in ARCH_KEYS.items():
        assert getattr(arch, field) == cfg[key], field


@pytest.mark.parametrize("tenant", ["dsv2lite-p512", "dsv2lite-p2048"])
def test_layer_rows_match_the_catalog_function(tenant):
    from repro.configs.registry import TENANT_ARCHS
    from repro.workloads.llm_zoo import llm_request_specs
    cfg = run.cell_spec(CELL)[2]
    cls = cfg["classes"][tenant]
    want, ds = rl.request_rows(cfg, cls["prompt_tokens"],
                               cls["decode_context"])
    got, ds_prog = llm_request_specs(TENANT_ARCHS[cls["arch"]],
                                     prompt=cls["prompt_tokens"],
                                     ctx=cls["decode_context"])
    i = cfg["tenants"].index(tenant)
    assert ds == ds_prog == cfg["tables"]["decode_start"][i]
    assert len(got) == len(want) == cfg["tables"]["n_layers"][i]
    for g, w in zip(got, want):
        assert (g.macs, g.w_bytes, g.in_bytes, g.out_bytes) == (
            w["macs"], w["w_bytes"], w["in_bytes"], w["out_bytes"]), g.name
    # the decode pass reads the latent cache, 576 values a token
    assert want[ds + 1]["in_bytes"] == (cfg["hidden_size"]
                                        + cls["decode_context"] * 576) * 2


@pytest.mark.parametrize("seed", SEEDS)
def test_streams_match_program_loadgen(seed):
    from repro.serving import LoadGenConfig, request_streams
    _, _, cfg, traffic = run.cell_spec(CELL)
    svc = lmserve_cell.build_service(cfg, traffic)
    n = loadgen_lm.requests_per_stream(cfg, traffic)
    lg = LoadGenConfig(
        scenario=traffic["scenario"], rate_scale=traffic["rate_scale"],
        n_requests=n, tenant_mix=tuple(traffic["tenant_mix"]),
        out_median=traffic["out_median"])
    want = request_streams(svc.env, lg, 5, seed=seed)
    got = loadgen_lm.streams(cfg, traffic, seed, 5)
    for w, g in zip(want, got):
        assert [r.tenant for r in w] == [cfg["tenants"][m]
                                         for m in g["model"]]
        assert [r.arrival_us for r in w] == g["arrival"].tolist()
        assert [r.deadline_us for r in w] == g["deadline"].tolist()
        assert [r.q_us for r in w] == [float(x) for x in g["q"]]
        assert [r.n_out for r in w] == g["n_out"].tolist()
        assert [r.tpot_us for r in w] == [float(x) for x in g["tpot"]]


def test_output_lengths_and_mix():
    _, _, cfg, traffic = run.cell_spec(CELL)
    g = loadgen_lm.stream(cfg, traffic, 4000, np.random.default_rng(3))
    assert g["n_out"].min() >= 8 and g["n_out"].max() <= 256
    assert abs(np.median(g["n_out"]) - 64) <= 4
    assert abs(np.mean(g["model"] == 1) - 0.25) < 0.03


# ---------------------------------------------------------------------------
# the program's tick against the reference, every tick of a session
# ---------------------------------------------------------------------------
def test_every_tick_of_a_session_matches_the_reference():
    _, _, cfg, traffic = _small()
    # long enough for requests to finish: a 512-token prefill alone is
    # 197 periods, and the first bursts arrive about 300 periods in
    traffic = dict(traffic, session_ticks=800)
    c = lmserve_cell.Cell(cfg, traffic, SEED)
    T = traffic["session_ticks"]
    store: dict = {}
    with serve_cell.record_ticks(set(range(T)), store):
        res = c.serve(0, T)
    acts, off = c.actions(store)
    assert off == 0 and sorted(store) == list(range(T))
    tb = rl.Tables(cfg)
    passes: dict = {}
    for i in range(T):
        pre, adm, _, post, out = store[i]
        for s in range(c.S):
            a = chk.flat_adm(adm, s)
            r = lchk.check_stream(tb, c.params_host,
                                  lchk.flat_queue(pre, s), a,
                                  lchk.flat_queue(post, s, out),
                                  acts[i][s], "exact")
            assert r["ok"], (i, s)
            # each pass that ended this tick, by request
            q, _ = rl.admit(tb, lchk.flat_queue(pre, s), a)
            p = lchk.flat_queue(post, s)
            ended = ((p["passes_left"] < q["passes_left"])
                     | (p["done"] & ~q["done"])) & q["occupied"]
            for j in np.flatnonzero(ended):
                key = (s, int(q["rid"][j]))
                passes[key] = passes.get(key, 0) + 1
    done = [(s, x) for s, comp in enumerate(res["completions"])
            for x in comp if not x["missed"]]
    assert len(done) >= 2 and any(
        c.cols[0][s]["n_out"][x["rid"]] > 2 for s, x in done)
    for s, x in done:
        assert passes[(s, x["rid"])] == c.cols[0][s]["n_out"][x["rid"]]
    assert lmserve_cell.accounting_errors(cfg, c.cols[0], res) == 0


# ---------------------------------------------------------------------------
# the cell's check: the program passes, its control and each fault fail
# ---------------------------------------------------------------------------
def test_program_is_correct():
    res = _run()
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"setup_s", "tick_p95_us",
                                   "serve_periods_per_s"}
    assert res["attempted"] > 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_fault_is_not_correct(fault):
    with faults.FAULTS[fault]():
        res = _run()
    assert not res["correct"], (fault, res["checks"])
    assert res["checks"]["tick_mismatches"]["value"] > 0, fault


def test_control_is_not_correct():
    _, _, cfg, traffic = _small()
    c = lmserve_cell.Cell(cfg, traffic, SEED)
    results, _ = c.window(0.0)
    nums = c.check(results, control=cfg["control"])
    assert nums["actor_gap"] > lmserve_cell.LIMITS["actor_gap"], nums


# ---------------------------------------------------------------------------
# the readers of the two new metrics
# ---------------------------------------------------------------------------
class _Ctx:
    ticks, stream_ticks = 10, 40

    def __init__(self, ms=0.0, **kw):
        self._ms = ms
        self.__dict__.update(kw)

    def scope_ms(self, scope):
        return self._ms if scope == "env.reenter" else 0.0


def test_lm_readers_read_nothing_where_nothing_is():
    assert run.read_layer("lm.reenter_ms", _Ctx()) is None
    assert run.read_layer("lm.passes_per_tick", _Ctx()) is None
    assert run.read_layer("lm.passes_per_tick", _Ctx(passes=None)) is None
    assert run.read_layer("lm.reenter_ms", _Ctx(ms=2.5)) == 0.25
    assert run.read_layer("lm.passes_per_tick", _Ctx(passes=20)) == 0.5
    # a counter that is there and reads none is a reading
    assert run.read_layer("lm.passes_per_tick", _Ctx(passes=0)) == 0.0


# ---------------------------------------------------------------------------
# set-up and the traced session
# ---------------------------------------------------------------------------
def test_short_warm_compiles_what_the_window_runs():
    from repro.telemetry.compiles import compile_counts, \
        install_compile_counter
    _, _, cfg, traffic = _small()
    install_compile_counter()
    c = lmserve_cell.Cell(cfg, traffic, SEED)
    c.warm(True)
    before = compile_counts()
    c.window(0.0)
    c.traced_window()
    spent = compile_counts(since=before)
    assert spent["trace_n"] == 0 and spent["compile_n"] == 0, spent


def test_traced_session_counts_its_decode_passes(monkeypatch):
    monkeypatch.setattr(serve_cell.peaks, "peak", lambda kind: None)
    _, _, cfg, traffic = _small()
    c = lmserve_cell.Cell(cfg, traffic, SEED)
    results, _ = c.traced_window()
    assert "device_tele" not in results[0]["stats"]     # the timed program
    evs = [dict(name="chipbench.window", start_ns=0, dur_ns=10**9,
                plane="/host:CPU")]
    ctx = c.layer_context(evs, results)
    assert ctx.passes > 0 and ctx.first_tokens > 0
    assert run.read_layer("lm.passes_per_tick", ctx) == (
        ctx.passes / (c.S * lmserve_cell.TRACE_TICKS))
    # a counted session that is not the traced one reads nothing
    other, _ = c.window(0.0, 50)
    assert c.layer_context(evs, other).passes is None
