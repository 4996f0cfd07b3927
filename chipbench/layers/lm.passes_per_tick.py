"""Decode passes committed per stream-tick of the traced window, from the
queue's device counter ``passes``: the traced session runs the timed
program, and the count is that of the same session served again with
the telemetry block (``lmserve_cell.LayerContext``). 0.0 where the
counter reads none, nothing where the program keeps no such counter."""


def read(ctx):
    passes = getattr(ctx, "passes", None)
    if passes is None or not ctx.stream_ticks:
        return None
    return passes / ctx.stream_ticks
