"""Device ms per tick under ``env.engine``: the contention engine's
event loop (``sim/engine.simulate_jax``) and its SA selects."""


def read(ctx):
    ms = ctx.scope_ms("env.engine")
    return ms / ctx.ticks if ms and ctx.ticks else None
