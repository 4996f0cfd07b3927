"""Device-resident telemetry primitives: counters and histograms.

Pure ``jnp`` pytree reducers designed to live *inside* jitted programs
— the fused training round's ``lax.scan`` carry and the serving tick's
donated queue pytree — and cross the host boundary only at the chunk /
flush boundaries those programs already pay for.  Nothing here may
force a sync: every op is shape-static, traceable, and composes with
``vmap`` / ``scan`` / ``shard_map`` like any other pytree math.

- **Counter**: a 0-d integer; :func:`counter_add` is associative, so
  accumulating per-round inside a scan equals one bulk add (tested in
  ``tests/test_telemetry.py``).
- **Histogram**: fixed-bucket counts over a static edge vector
  (:func:`hist_init` / :func:`hist_add`).  Bucket ``i`` counts values
  in ``[edges[i-1], edges[i])`` with bucket ``0`` the underflow
  (``v < edges[0]``) and bucket ``len(edges)`` the overflow
  (``v >= edges[-1]``).  The add is a one-hot masked reduction, not a
  scatter: XLA CPU lowers batched scatters to serial loops (the same
  trick as the engine's segment ops and the serving queue's
  admission).

Bit-neutrality contract: these reducers only ever *read* the values
the surrounding program already computes; enabling them must not
change any other output bit (asserted for the fused round and the
serving tick in ``tests/test_telemetry.py``).
"""
from __future__ import annotations

import jax.numpy as jnp

# default edge vectors for the in-graph aggregates the fused round and
# serving tick maintain (see repro.core.train / repro.core.serve)
SLA_EDGES = (0.2, 0.4, 0.6, 0.8, 0.9, 0.95, 0.99)
REWARD_EDGES = (-4.0, -2.0, -1.0, -0.5, 0.0, 0.5, 1.0, 2.0, 4.0)


# ---------------------------------------------------------------------------
# counters
# ---------------------------------------------------------------------------
def counter_init(dtype=jnp.int32) -> jnp.ndarray:
    """A zeroed 0-d counter."""
    return jnp.zeros((), dtype)


def counter_add(c: jnp.ndarray, n=1) -> jnp.ndarray:
    """``c + n`` in the counter's dtype (associative scan reducer)."""
    return c + jnp.asarray(n).astype(c.dtype)


# ---------------------------------------------------------------------------
# fixed-bucket histograms
# ---------------------------------------------------------------------------
def hist_init(edges) -> dict[str, jnp.ndarray]:
    """Empty histogram over ``len(edges) + 1`` buckets.

    ``edges`` must be strictly increasing; the returned pytree is
    ``dict(edges (E,) f32, counts (E + 1,) i32)``.
    """
    e = jnp.asarray(edges, jnp.float32)
    if e.ndim != 1 or e.shape[0] < 1:
        raise ValueError(f"edges must be a non-empty 1-D vector, "
                         f"got shape {e.shape}")
    return dict(edges=e, counts=jnp.zeros((e.shape[0] + 1,), jnp.int32))


def hist_add(h: dict, values, weights=None) -> dict:
    """Fold a block of values into the histogram (traceable).

    ``values`` is flattened; ``weights`` (optional, same size) are
    summed per bucket instead of unit counts.  One-hot masked
    reduction — no scatter.
    """
    v = jnp.ravel(jnp.asarray(values, jnp.float32))
    idx = jnp.searchsorted(h["edges"], v, side="right")
    hot = idx[:, None] == jnp.arange(h["counts"].shape[0])[None, :]
    if weights is None:
        add = jnp.sum(hot, axis=0, dtype=h["counts"].dtype)
    else:
        w = jnp.ravel(jnp.asarray(weights))
        add = jnp.sum(jnp.where(hot, w[:, None], 0), axis=0,
                      dtype=h["counts"].dtype)
    return dict(edges=h["edges"], counts=h["counts"] + add)


# ---------------------------------------------------------------------------
# canonical in-graph aggregates for the fused training round
# ---------------------------------------------------------------------------
def round_telemetry(per_episode_sla, rewards, committed, replay_size,
                    replay_capacity: int) -> dict:
    """The fused round's telemetry block (pure; rides the round's
    existing metrics transfer — see ``repro.core.train._round_body``).

    Returns flat ``tele_*`` leaves so the driver can serialize them
    without knowing histogram internals: SLA histogram counts over
    :data:`SLA_EDGES`, per-period reward histogram counts over
    :data:`REWARD_EDGES`, committed-sub-job counter, and the replay
    ring's fill fraction gauge.
    """
    sla_h = hist_add(hist_init(SLA_EDGES), per_episode_sla)
    rew_h = hist_add(hist_init(REWARD_EDGES), rewards)
    return dict(
        tele_sla_hist=sla_h["counts"],
        tele_reward_hist=rew_h["counts"],
        tele_committed=jnp.sum(jnp.asarray(committed)).astype(jnp.int32),
        tele_replay_fill=(jnp.asarray(replay_size, jnp.float32)
                          / jnp.float32(replay_capacity)),
    )


# leaf names round_telemetry emits — consumers (driver, sharded-round
# reductions) iterate these instead of hard-coding
ROUND_TELE_COUNTS = ("tele_sla_hist", "tele_reward_hist", "tele_committed")
ROUND_TELE_GAUGES = ("tele_replay_fill",)
ROUND_TELE_KEYS = ROUND_TELE_COUNTS + ROUND_TELE_GAUGES
