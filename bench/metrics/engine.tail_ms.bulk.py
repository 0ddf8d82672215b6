"""Streaming engine (``core/stream_engine.py``): own device time of the
operations under the ``dco.tail`` scope (tail gather and completion, and
the full-scan body's tail product) per whole ``bench.step`` span of the
traced stretch, mean, in milliseconds (``bench.stages.scope_ms``).  Device
trace.  None where the trace carries no such scope."""
from bench import stages


def read(ctx):
    return stages.scope_ms(ctx.trace, "dco.tail")
