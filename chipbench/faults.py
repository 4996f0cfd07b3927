"""Faults planted underneath the serving path, for the checks' tests and
for reading what each fault makes the compared numbers read.

Each is a context manager that patches the program's tick or actor for
services built inside it (compiled ticks are cached per service).

- ``unchanged``: the tick returns the queues it was given;
- ``half``: the tick leaves the second half of the streams unchanged;
- ``altered``: the actor's SA choice moves to the next SA, where the
  decision is produced.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def _patch(obj, name, value):
    orig = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, orig)


def _wrap_tick(keep):
    """Patch ``make_serving_tick`` so that stream ``s`` of the queues the
    tick returns is the queue it was given wherever ``keep(S)[s]``."""
    import jax
    import jax.numpy as jnp
    import repro.core.serve as cs
    orig = cs.make_serving_tick

    def make(*a, **k):
        tick = orig(*a, **k)

        def broken(params, queues, adm, key):
            pre = jax.tree.map(jnp.copy, queues)
            post, out = tick(params, queues, adm, key)
            S = jax.tree.leaves(post)[0].shape[0]
            m = jnp.asarray(keep(S))
            sel = lambda p, q: jnp.where(
                m.reshape((S,) + (1,) * (p.ndim - 1)), q, p)
            return jax.tree.map(sel, post, pre), out
        return broken
    return _patch(cs, "make_serving_tick", make)


def unchanged():
    import numpy as np
    return _wrap_tick(lambda S: np.ones(S, bool))


def half():
    import numpy as np
    return _wrap_tick(lambda S: np.arange(S) >= S // 2)


def altered():
    import jax.numpy as jnp
    import repro.core.serve as cs
    orig = cs.specialist_act

    def act_fn(pcfg):
        act = orig(pcfg)

        def shifted(*a):
            out, prio, sa = act(*a)
            return out, prio, (sa + 1) % (out.shape[-1] - 1)
        return shifted
    return _patch(cs, "specialist_act", act_fn)


FAULTS = {"unchanged": unchanged, "half": half, "altered": altered}
