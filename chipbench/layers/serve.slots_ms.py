"""Device ms per tick under ``env.slots``: slot packing
(``sim/env.build_slots``: the deadline sort and ``searchsorted``)."""


def read(ctx):
    ms = ctx.scope_ms("env.slots")
    return ms / ctx.ticks if ms and ctx.ticks else None
