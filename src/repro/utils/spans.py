"""Names of the served search path's profiler spans and device scopes.

A host span is a ``jax.profiler.TraceAnnotation``: with no profiler session
active it costs one TraceMe check, and under ``jax.profiler`` it lands in
the same trace as the device operations, on the same clock.  The serving
path records one set per served batch, never one per request; ``first_rid``
on ``search.step`` ties a request's ticket to the step that served it.

A device scope is a ``jax.named_scope`` around one stage of the streaming
engine: it prefixes the ``op_name`` metadata of the operations the stage
traces into, and changes nothing else in the compiled program.  An op
belongs to the innermost scope on its path.
"""
from __future__ import annotations

#: host spans, one set per ``SearchService.step`` that serves a batch
SPANS = {
    "search.step": "SearchService.step for a batch it serves, pop to ticket "
                   "fill (args: step, queries, slots, first_rid)",
    "search.batch": "expiry of queued requests, pop, stack and pad "
                    "(args: expired)",
    "search.prep": "the facade's query checks, then the backend's config "
                   "lookup, host rotation and the copy of queries and "
                   "extras to the device",
    "search.dispatch": "the call into stream_topk until it returns "
                       "(args: chunks, full_chunks)",
    "search.seed_sync": "host read of the adaptive seed's pass fractions",
    "search.fetch": "copy of the engine's outputs to the host, through "
                    "block_until_ready",
    "search.finish": "ScanStats, certificate and stats.extra assembly "
                     "(args: shared_block_share on the streaming engine)",
    "search.tickets": "the service's fill loop over the batch (args: served)",
    "search.group_sync": "host sync after one anytime block group "
                         "(args: group)",
}

#: device scopes, one per stage of ``core.stream_engine``
SCOPES = {
    "dco.seed": "the adaptive policy's pre-scan seed (_seed_eval)",
    "dco.lead": "stage-1 lead distances and the screen: the dco_scan kernels, "
                "the jnp lead product, _lead_partial, the PDX screen",
    "dco.compact": "the chunk's survivor union and its shared row "
                   "selection, or the per-query survivor top_k and the "
                   "certificate's observer column",
    "dco.tail": "tail gather and completion; the full-scan tail product",
    "dco.merge": "the running top-k merge and the tau update",
    "dco.scan": "the scan over row blocks itself: its per-block slices of "
                "the layout and the carry's bookkeeping outside the stages",
}


def span(name: str, **args):
    """The host span ``name`` (a key of ``SPANS``) with ``args`` as its
    counters; ``set_metadata`` on the returned object adds counters known
    only inside it."""
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name, **args)
