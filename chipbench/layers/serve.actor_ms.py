"""Device ms per tick under ``env.act``: the LSTM actor and its heads."""


def read(ctx):
    ms = ctx.scope_ms("env.act")
    return ms / ctx.ticks if ms and ctx.ticks else None
