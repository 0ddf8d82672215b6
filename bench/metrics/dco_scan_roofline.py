"""Kernels (``kernels/dco_scan.py``): the stage-1 screen's share of its
roofline.  On each chip the cell used, the least time of the screen work of
every whole ``bench.step`` span in the traced stretch
(``bench.work.screen_work`` from the rows the chip holds,
``bench.work.shard_rows``: the corpus divided over those chips; ``d1`` and
the step's real queries; bytes bound it at about 8 flop/B against a ridge
near 240); those least times summed over the chips, over the summed device
time of the events named below inside those spans on every chip.  None
where no such event ran."""
from bench import tracing, work

#: device events that do the stage-1 screen
EVENTS = ("dco_scan",)


def read(ctx):
    pol = ctx.config["policy"]
    chips = tracing.used_chips(ctx.trace)
    if not chips:
        return None
    rows = work.shard_rows(int(ctx.config["data"]["n"]), len(chips))
    least = spent = 0.0
    for ops in chips.values():
        kern = tracing.matching(ops, EVENTS)
        for _, a, b, stats in ctx.trace.step_spans():
            ns = sum(min(e1, b) - max(e0, a) for _, e0, e1 in kern
                     if e0 < b and e1 > a)
            if ns <= 0:
                continue
            flop, nbytes = work.screen_work(rows, int(pol["d1"]),
                                            int(stats["queries"]),
                                            int(pol["query_chunk"]))
            least += work.least_time_s(flop, nbytes, ctx.peaks)[0]
            spent += ns / 1e9
    return 100.0 * least / spent if spent else None
