"""Streaming engine on a mesh (``core/jax_engine.py``,
``make_distributed_topk``): own device time of the operations under the
``dco.gather`` scope (the ``all_gather`` of each shard's top-k, the global
``top_k`` and the ``psum``/``pmin`` of the shards' counters and
certificates) per whole ``bench.step`` span of the traced stretch, mean
over the cell's chips, in milliseconds (``bench.stages.scope_ms``).
Device trace.  None where the trace carries no such scope, as on one
chip or on a program without it."""
from bench import stages


def read(ctx):
    return stages.scope_ms(ctx.trace, "dco.gather")
