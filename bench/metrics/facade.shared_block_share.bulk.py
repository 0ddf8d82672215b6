"""Facade (``api/backends.py``): the share of scanned row blocks whose
screen survivors a query's chunk completed through the chunk-shared path,
as the ``search.finish`` span's ``shared_block_share`` argument gives it
(on a mesh, over every shard's blocks), mean over the ``search.finish``
spans inside whole ``bench.step`` spans of the traced stretch, in
percent.  Program span.  None where no such span carries the argument."""

ARG = "shared_block_share"


def read(ctx):
    tr = ctx.trace
    steps = tr.step_spans()
    vals = [float(st[ARG]) for name, a, b, st in tr.spans
            if name == "search.finish" and ARG in st
            and any(s[1] <= a and b <= s[2] for s in steps)]
    return 100.0 * sum(vals) / len(vals) if vals else None
