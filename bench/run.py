"""Run one cell of the benchmark once, on the chips this process sees.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (``setup_s``, from the start of this script to the first timed
request): the configuration's corpus and query pools are drawn on the device
from ``--seed`` and copied to the host, the program builds its own served
index over them (``open_index(..., backend="jax", serving=True)``, method
fit included; on a cell of four chips the corpus rows are divided over
them), and every batch shape the window uses is served once per query
kind.  The window then offers the traffic mix's load for ``--seconds``.
With ``--trace 1`` a short traced stretch of the same load follows the
window, and the per-layer metrics are reported instead of the end-to-end
ones.

After the window the program's state is freed and a sample of the window's
answers, drawn from the seed, is compared with a brute force of its own
(``bench/reference.py``).  The numbers compared are printed beside their
limits as the last lines on standard error and under ``checks``, the last
key of the result.  The last line of standard output is the result JSON.
Without a TPU, or with fewer chips than the cell asks for, the script exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[0] = str(ROOT)         # not bench/: its modules are bench.*
    sys.path.insert(1, str(ROOT / "src"))

import numpy as np  # noqa: E402

CHECK_SAMPLE = 1024       # window answers compared with the reference
TRACE_LEAD_S = 0.5        # load before the traced stretch is measured
TRACE_SECONDS = 1.5       # length of the traced stretch
# Both are for one chip; a cell of n chips traces 1/n of them.  The
# profiler's stop_trace, the trace's reading and the readers each take
# time in step with the device ops traced: on a v5e host, 2.7M ops of one
# chip (2 s of the 1M x 768 cell) took 84, 9 and 17 s, 10M ops of four
# chips 184, 34 and 65 s, so a four-chip trace as long as one chip's
# would not end inside a run's time limit.


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def fact(label: str, value) -> None:
    print(f"[setup] {label}: {value}", file=sys.stderr, flush=True)


@dataclass
class Context:
    """What a per-layer metric's reader gets."""

    window: object        # bench.window.Window
    trace: object         # bench.stages.ProgramTrace, or None without
                          # --trace 1
    config: dict
    traffic: dict
    peaks: dict | None


def devices(chips: int, require_tpu: bool):
    import jax
    if require_tpu and jax.default_backend() != "tpu":
        raise NoChip(f"no TPU found (JAX default backend is "
                     f"{jax.default_backend()!r})")
    devs = jax.devices()
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX finds "
                     f"{len(devs)}")
    return devs[:chips]


def enable_cache() -> str:
    """JAX's persistent compilation cache in the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), every program written to it."""
    import jax
    from repro.utils.compile_cache import enable_compile_cache
    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def check_answers(window, data, cfg: dict, seed: int,
                  control: str | None = None):
    """Numbers of a seeded sample of the window's served answers against
    the reference, each beside its limit; ``control`` (a precision of
    ``bench.reference.matmul``) puts the reference's control in the
    program's place."""
    from bench import reference
    limits = cfg["limits"]
    done = window.done()
    rng = np.random.default_rng([seed, 1])
    pick = rng.choice(len(done), min(CHECK_SAMPLE, len(done)), replace=False)
    reqs = [done[j] for j in sorted(pick)]
    nums = {"wrong_ranks": 0, "dist_err_ulps": 0.0}   # nothing served
    if reqs:
        Q = np.stack([r.ticket.q for r in reqs])
        ref_ids, ref_d = reference.reference_topk(data.X, Q, int(cfg["k"]))
        if control:
            ids, dists = reference.control_topk(data.X, Q, int(cfg["k"]),
                                                control)
        else:
            ids = np.stack([r.ticket.ids for r in reqs])
            dists = np.stack([r.ticket.dists for r in reqs])
        nums = reference.compare(ids, dists, data.X, Q, ref_ids, ref_d)
    nums["failed"] = window.failed()
    checks = {name: {"value": nums[name], "limit": limits[name]}
              for name in ("wrong_ranks", "dist_err_ulps", "failed")}
    return checks, len(reqs)


def end_to_end(window, setup_s: float) -> dict:
    """Every end-to-end metric the harness can compute for this window."""
    from bench.window import percentile
    out = {"qps": window.qps(), "setup_s": setup_s}
    if window.loop == "open":
        lat = window.latencies_s() * 1e3
        out["p50_ms"] = percentile(lat, 50)
        out["p99_ms"] = percentile(lat, 99)
    return out


def traced_stretch(svc, pool, traffic: dict, rng, chips: int = 1):
    """The cell's load for a short stretch under the profiler: a lead-in,
    then ``TRACE_SECONDS / chips`` inside a ``bench.window`` span; the
    trace as ``bench.stages.load`` reads it, with the program's spans and
    scopes."""
    import jax
    from bench import driver, stages, tracing
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        t0 = time.perf_counter()
        jax.profiler.start_trace(tmp)
        try:
            driver.run_window(svc, pool, traffic, TRACE_LEAD_S / chips, rng,
                              trace=True)
            with tracing.span("bench.window", True):
                driver.run_window(svc, pool, traffic, TRACE_SECONDS / chips,
                                  rng, trace=True)
            t1 = time.perf_counter()
        finally:
            jax.profiler.stop_trace()
        t2 = time.perf_counter()
        tr = stages.load(tmp)
        fact("traced stretch s, stop_trace s, reading its trace s",
             f"{t1 - t0} {t2 - t1} {time.perf_counter() - t2} "
             f"({sum(map(len, tr.ops.values()))} device ops on "
             f"{len(tracing.used_chips(tr))} chips)")
        return tr
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


@dataclass
class Prepared:
    """A cell's service after set-up."""

    cell: object          # bench.manifest.Cell
    devs: list
    data: object          # bench.datagen.Data
    svc: object           # the program's SearchService
    clock: object         # bench.driver.CompileClock
    pool: np.ndarray      # the traffic mix's query pool


def prepare(root, workload: str, seed: int, *, require_tpu: bool = True,
            cache: bool = True) -> Prepared:
    """Set-up of one cell: data, the program's served index, warm-up."""
    from bench import datagen, driver, manifest, work

    cell = manifest.resolve(root, workload)
    devs = devices(cell.chips, require_tpu)
    if require_tpu:
        work.peaks(devs[0].device_kind)      # an unknown chip fails here
    fact("device", f"{devs[0].platform} {devs[0].device_kind} x{len(devs)}")
    if cache:
        fact("compile cache", enable_cache())
    clock = driver.CompileClock()
    try:
        t0 = time.perf_counter()
        data = datagen.generate(cell.config["data"], seed)
        gc.collect()
        fact("data generation s", time.perf_counter() - t0)
        t0 = time.perf_counter()
        svc = driver.open_service(cell.config, cell.traffic, data.X, seed,
                                  devs)
        fact("open_index s", time.perf_counter() - t0)
        t0 = time.perf_counter()
        driver.warm_up(svc, data.pools)
        fact("warm-up s", time.perf_counter() - t0)
        fact("compiles in set-up", f"{clock.compiles} "
             f"({clock.seconds} s backend compile)")
    except BaseException:
        clock.close()
        raise
    return Prepared(cell, devs, data, svc, clock,
                    data.pools[cell.traffic["queries"]])


def run_cell(root, workload: str, seed: int, seconds: float, trace: bool,
             *, require_tpu: bool = True, cache: bool = True,
             control: str | None = None) -> dict:
    """One run of ``workload``; returns the result object.  ``control``
    (a precision, as the configuration's ``control_precision``) puts the
    reference's control in the program's place for the comparison: the
    control's own runs and test; the benchmark never sets it."""
    from bench import driver, manifest, tracing, work

    p = prepare(root, workload, seed, require_tpu=require_tpu, cache=cache)
    cell, dev = p.cell, p.devs[0]
    try:
        rng = np.random.default_rng([seed, 0])
        setup_s = time.perf_counter() - T_START
        n0 = p.clock.compiles
        window = driver.run_window(p.svc, p.pool, cell.traffic, seconds, rng)
        window.compiles = p.clock.compiles - n0
        fact("compiles in window", window.compiles)
        late = window.lateness_s()
        if late.size:
            fact("driver lateness ms p50/p99/max",
                 f"{np.percentile(late, 50) * 1e3} "
                 f"{np.percentile(late, 99) * 1e3} {late.max() * 1e3}")
        fact("window", f"{len(window.requests)} requests, "
             f"{len(window.steps)} steps")
        tr = (traced_stretch(p.svc, p.pool, cell.traffic, rng, len(p.devs))
              if trace else None)
        mem = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in p.devs]
    finally:
        p.clock.close()
    p.svc = None
    gc.collect()
    t0 = time.perf_counter()
    checks, n_checked = check_answers(window, p.data, cell.config, seed,
                                      control=control)
    fact("answers compared", f"{n_checked} in {time.perf_counter() - t0} s")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(p.devs), "memory_peak_bytes": max(mem),
              "memory_peak_bytes_per_chip": mem}
    out = {"correct": all(c["value"] <= c["limit"] for c in checks.values()),
           "attempted": len(window.requests), "failed": window.failed()}
    if trace:
        peaks = work.peaks(dev.device_kind) if require_tpu else None
        ctx = Context(window, tr, cell.config, cell.traffic, peaks)
        metrics = {}
        t0 = time.perf_counter()
        for m in cell.per_layer:
            v = manifest.reader(root, m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        fact("per-layer readers s", time.perf_counter() - t0)
        device["busy_s"] = tracing.device_busy_s(tr)
        device["window_s"] = tracing.window_s(tr)
        out["metrics"] = metrics
        out["device"] = device
        out["breakdown"] = {"device_ops": tracing.top_ops(tr),
                            "idle_gaps": tracing.idle_gaps(tr)}
    else:
        values = end_to_end(window, setup_s)
        out["metrics"] = {m["name"]: {"value": values[m["name"]],
                                      "unit": m["unit"]}
                          for m in cell.end_to_end}
        out["device"] = device
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(ROOT, args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except NoChip as exc:
        print(f"bench/run.py: {exc}; nothing was run", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
