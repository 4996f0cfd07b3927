"""Compile the main path's chip programs for a TPU v5e that is described,
not attached: the Pallas LSTM kernels at the policy's real widths and
the 96-stream serving tick.  Nothing runs; the TPU compiler refuses
what a chip would refuse (tiling, VMEM, HBM), at no chip time.

The topology is described inside a module fixture, never at import, so
every pytest-xdist worker collects the same tests and only the worker
that runs this file loads the TPU library.  The persistent compilation
cache is off around these compiles: an entry written for a described
chip cannot be read back without one.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.lstm_cell.lstm_cell import lstm_cell_pallas
from repro.kernels.lstm_seq.lstm_seq import lstm_seq_pallas

# the policy's LSTM at rl_train's widths: R+1 = 97 timesteps,
# F = 4 + 2M = 16 on the 6-SA paper6 fleet, hidden 64
T, F, H = 97, 16, 64


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    # the TPU library would otherwise write its logs outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler here: nothing to test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("B", [1, 128])
def test_lstm_seq_compiles_for_v5e(one_chip, B):
    args = (_spec((T, B, F), jnp.float32, one_chip),
            _spec((T, B), jnp.bool_, one_chip),
            _spec((F, 4, H), jnp.float32, one_chip),
            _spec((H, 4, H), jnp.float32, one_chip),
            _spec((4, H), jnp.float32, one_chip))
    compiled = lstm_seq_pallas.lower(*args, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_lstm_cell_compiles_for_v5e(one_chip):
    B = 128
    args = (_spec((B, F), jnp.float32, one_chip),
            _spec((B, H), jnp.float32, one_chip),
            _spec((B, H), jnp.float32, one_chip),
            _spec((F, 4, H), jnp.float32, one_chip),
            _spec((H, 4, H), jnp.float32, one_chip),
            _spec((4, H), jnp.float32, one_chip))
    compiled = lstm_cell_pallas.lower(*args, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_serving_tick_compiles_for_v5e(one_chip):
    """The 96-stream single-dispatch tick of the specialist policy at
    rl_train's env widths (max_rq 96, max_jobs 64, 60 periods)."""
    from repro.core import policy as P
    from repro.core.serve import make_serving_tick, queue_init_batch
    from repro.launch.rl_train import TrainConfig, build_env
    S, K = 96, 8
    env = build_env(TrainConfig())
    pcfg = P.PolicyConfig(feat_dim=env.feat_dim, act_dim=env.act_dim,
                          hidden=64)
    tick = make_serving_tick(env, kind="specialist", pcfg=pcfg, streams=S)
    on_chip = lambda tree: jax.tree.map(
        lambda x: _spec(x.shape, x.dtype, one_chip), tree)
    params = on_chip(jax.eval_shape(
        lambda: P.init_actor(jax.random.PRNGKey(0), pcfg)))
    queues = on_chip(jax.eval_shape(lambda: queue_init_batch(env, S)))
    adm = dict(model=_spec((S, K), jnp.int32, one_chip),
               arrival=_spec((S, K), jnp.float32, one_chip),
               deadline=_spec((S, K), jnp.float32, one_chip),
               q=_spec((S, K), jnp.float32, one_chip),
               rid=_spec((S, K), jnp.int32, one_chip),
               valid=_spec((S, K), jnp.bool_, one_chip))
    key = _spec((2,), jnp.uint32, one_chip)
    compiled = tick.lower(params, queues, adm, key).compile()
    mem = compiled.memory_analysis()
    # the whole tick fits a 16 GB v5e many times over
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 2**30


def test_lm_serving_tick_compiles_for_v5e(one_chip):
    """The 96-stream tick over whole DeepSeek-V2-Lite requests (jobs that
    re-enter) at the paper6 widths, as the benchmark times and traces it
    (no telemetry block); a fusion the chip runs keeps the re-entry's
    scope, which ``lm.reenter_ms`` reads."""
    import re

    import numpy as np

    from repro.core import policy as P
    from repro.core.serve import make_serving_tick, queue_init_batch
    from repro.serving.queue import pack_admissions
    from repro.sim.env import EnvConfig, SchedulingEnv
    from repro.workloads import build_llm_registry
    S, K = 96, 8
    env = SchedulingEnv(build_llm_registry("lm_dsv2lite"),
                        EnvConfig(max_rq=96, max_jobs=64))
    pcfg = P.PolicyConfig(feat_dim=env.feat_dim, act_dim=env.act_dim,
                          hidden=64)
    tick = make_serving_tick(env, kind="specialist", pcfg=pcfg, streams=S)
    on_chip = lambda tree: jax.tree.map(
        lambda x: _spec(x.shape, x.dtype, one_chip), tree)
    params = on_chip(jax.eval_shape(
        lambda: P.init_actor(jax.random.PRNGKey(0), pcfg)))
    queues = on_chip(jax.eval_shape(
        lambda: queue_init_batch(env, S)))
    adm = on_chip({k: np.stack([v] * S) for k, v in
                   pack_admissions([], K).items()})
    key = _spec((2,), jnp.uint32, one_chip)
    compiled = tick.lower(params, queues, adm, key).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 2**30
    assert re.search(r'^\s*(?:ROOT\s+)?%?[\w.\-]+ = .* fusion\(.*'
                     r'op_name="[^"]*env\.reenter', compiled.as_text(),
                     re.M)
