"""Streaming engine (``core/stream_engine.py``): device busy time (the
union of device operation intervals) inside each ``bench.step`` span of the
traced stretch, mean per step, in milliseconds.  Device trace."""
from bench import tracing


def read(ctx):
    ms = tracing.step_busy_ms(ctx.trace)
    return sum(ms) / len(ms) if ms else None
