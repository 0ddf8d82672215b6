"""Streaming engine on a mesh (``core/jax_engine.py``,
``make_distributed_topk``): how long the fastest shard waits on the
slowest.  In each whole ``bench.step`` span of the traced stretch, the
start of each chip's first operation under the ``dco.gather`` scope (the
gather of the shards' top-k, which no chip can pass before every chip has
reached it); the latest of those starts minus the earliest, mean over the
steps in which at least two chips ran the scope, in milliseconds.  Device
trace.  None where fewer than two chips ran the scope in any step, or
where the trace carries no op names."""
import bisect

from bench import stages, tracing

SCOPE = "dco.gather"


def read(ctx):
    tr = ctx.trace
    scopes = getattr(tr, "scopes", None)
    if not scopes:
        return None
    starts = []                  # per chip: sorted starts of its scope's ops
    for dev, ops in tracing.used_chips(tr).items():
        starts.append(sorted(op[1] for op, name in zip(ops, scopes.get(dev, []))
                             if stages.scope_of(name) == SCOPE))
    skews = []
    for _, a, b, _ in tr.step_spans():
        first = []
        for s in starts:
            j = bisect.bisect_left(s, a)
            if j < len(s) and s[j] < b:
                first.append(s[j])
        if len(first) >= 2:
            skews.append(max(first) - min(first))
    return sum(skews) / len(skews) / 1e6 if skews else None
