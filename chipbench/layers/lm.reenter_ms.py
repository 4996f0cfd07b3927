"""Device ms per tick under ``env.reenter`` in the timed tick: the
instructions XLA leaves named for the pass ends, re-entries and
two-limit bookkeeping of jobs that re-enter (``sim/env.SchedulingEnv``).
XLA fuses most of that work into commit's fusions, named for their
roots; on v5e one fusion, the re-entry's select, keeps the name."""


def read(ctx):
    ms = ctx.scope_ms("env.reenter")
    return ms / ctx.ticks if ms and ctx.ticks else None
