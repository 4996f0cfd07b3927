"""Device ms per tick under ``serving.admit`` and ``serving.retire``."""


def read(ctx):
    ms = ctx.scope_ms("serving.admit") + ctx.scope_ms("serving.retire")
    return ms / ctx.ticks if ms and ctx.ticks else None
