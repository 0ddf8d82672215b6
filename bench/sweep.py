"""Find the knee of an online cell: one set-up, then its open-loop traffic at
each of several offered rates, one window each.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 200,300,400

The knee is the highest offered rate at which the cell keeps up: the
requests resolved inside the window are at least 98% of those offered, and
no more than two batches (``2 * slots``) of the window's requests are still
unresolved when it closes (the requests due during the last step or two
are in flight at any rate).  Prints one JSON line per rate, then one with
the knee and the rate at four fifths of it; the cell's traffic file takes
that rate by hand.  Without a TPU the script exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))

import numpy as np  # noqa: E402

KEEP_UP = 0.98


def point(window, rate: float, slots: int) -> dict:
    """What one window at ``rate`` shows."""
    from bench.window import percentile
    reqs = window.requests
    inside = sum(r.ticket.t_done is not None and r.ticket.t_done <= window.t_end
                 for r in reqs)
    lat = window.latencies_s() * 1e3
    return {"rate_qps": rate, "offered": len(reqs),
            "resolved_in_window": inside,
            "backlog_at_close": len(reqs) - inside,
            "achieved_qps": inside / window.seconds,
            "p50_ms": percentile(lat, 50), "p99_ms": percentile(lat, 99),
            "steps": len(window.steps),
            "mean_batch": float(np.mean([s.served for s in window.steps])),
            "keeps_up": inside >= KEEP_UP * len(reqs)
            and len(reqs) - inside <= 2 * slots}


def main(argv=None) -> int:
    from bench import driver
    from bench.run import NoChip, prepare

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, queries/s")
    args = ap.parse_args(argv)
    try:
        p = prepare(ROOT, args.workload, args.seed)
    except NoChip as exc:
        print(f"bench/sweep.py: {exc}; nothing was run", file=sys.stderr)
        return 2
    if p.cell.traffic["loop"] != "open":
        print("bench/sweep.py: the cell's traffic is not an open loop",
              file=sys.stderr)
        return 2
    rng = np.random.default_rng([args.seed, 2])
    knee = None
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            w = driver.open_loop(p.svc, p.pool, rate, args.seconds, rng)
            pt = point(w, rate, p.svc.slots)
            print(json.dumps(pt), flush=True)
            if pt["keeps_up"]:
                knee = rate
    finally:
        p.clock.close()
    print(json.dumps({"workload": args.workload, "knee_qps": knee,
                      "rate_at_four_fifths": None if knee is None
                      else 0.8 * knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
