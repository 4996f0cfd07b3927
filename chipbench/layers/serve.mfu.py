"""The actor's matmul operations per stream-tick (``flops.py``) times
stream-ticks per second of the traced window, over the chip's peak, in %."""


def read(ctx):
    if not ctx.window_s or not ctx.stream_ticks:
        return None
    rate = ctx.flops_per_stream_tick * ctx.stream_ticks / ctx.window_s
    return 100.0 * rate / ctx.peak["bf16_flop_per_s"]
