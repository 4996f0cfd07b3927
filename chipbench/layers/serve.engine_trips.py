"""Trips per tick of the engine's batched event loop, counted on the
device trace: the runs of one instruction of the loop body under
``env.engine`` (the loop runs until the slowest stream is done)."""
import span_idle


def read(ctx):
    trips = span_idle.engine_trips(ctx.devs, ctx.hlo_names)
    return trips / ctx.ticks if trips and ctx.ticks else None
