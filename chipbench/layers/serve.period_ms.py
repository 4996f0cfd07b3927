"""Device ms per tick under ``serving.period`` (engine, actor, encode)."""


def read(ctx):
    ms = ctx.scope_ms("serving.period")
    return ms / ctx.ticks if ms and ctx.ticks else None
