"""Smoke run of the certified streaming search on a TPU, through the facade.

One chip (the default) runs two phases on the ``wikipedia`` family of
``repro.vecdata`` at 1,000,000 x 768 — the shape and size of VectorDBBench's
768-d 1M (Cohere) case and of the paper's Table IV Wikipedia family:

  (a) serving: PDScanning+ on a flat index, default ``SchedulePolicy``
      (streaming engine, d1=128, row_block=4096, the ``dco_scan`` kernel),
      served by ``open_index(..., serving=True)``; 64 in-distribution
      queries are submitted and drained.  Every ticket must end ``done``
      and certified, with the ids of a float64 brute force.
  (b) the other two kernels: one ``search()`` batch on the PDX layout
      (``dim_groups=4``, the ``dco_scan_grouped`` kernel; exact, certified)
      and one DDCopq batch (the ``pq_lookup`` kernel; recall@10 >= 0.9).
      DDCopq's threshold is calibrated on the 36 queries the run does not
      evaluate, against their true k-th neighbour over the whole corpus
      (``train(..., candidates_per_query=N)``): the default calibration,
      the k-th of 2,048 random rows, sets a threshold for neighbours far
      farther than the true ones at 1M rows, and recall falls with N.

``--chips 4`` runs only the mesh-sharded flat corpus (``open_index(...,
mesh=make_host_mesh(4, 1))``) at four times the rows, against the same
float64 reference.

Ids must equal the reference's except where two distances tie at f32
resolution; returned distances must agree with the float64 distances of
the returned ids to within ``F32_ULPS`` ulps of the norms the f32
distance form cancels.  Any failed check exits non-zero.  The script exits
non-zero without a result line when JAX's default backend is not a TPU.
Setup facts go to earlier lines; the last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

  python chip_smoke.py [--seed 0] [--chips 4]
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from collections import Counter
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.api import SchedulePolicy, SearchSession, open_index  # noqa: E402
from repro.core.methods import make_method  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.utils.compile_cache import enable_compile_cache  # noqa: E402
from repro.vecdata import load_dataset  # noqa: E402
from repro.vecdata.synthetic import recall_at_k  # noqa: E402

K = 10
N_QUERIES = 64
SLOTS = 16
SCALE = 20              # wikipedia family: 50,000 x 20 = 1,000,000 rows
F32_ULPS = 16           # distance tolerance, in f32 ulps of ||x||^2+||q||^2
OPQ_RECALL = 0.9        # tests/test_stream_engine.py's estimator bar


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def require(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def fact(label: str, value) -> None:
    """A labelled setup fact (not a metric)."""
    print(f"[setup] {label}: {value}", flush=True)


class CompileClock:
    """Sums JAX's compile-phase durations (trace, lowering, backend
    compile) reported through ``jax.monitoring``."""

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration
            self.compiles += event.endswith("backend_compile_duration")


def reference_topk(X, Q, k: int, chunk: int = 1 << 16):
    """Float64 brute force, chunked over rows: (ids, squared dists), each
    (nq, k), ascending."""
    Qd = Q.astype(np.float64)
    qn = (Qd ** 2).sum(1)[:, None]
    best_d = np.empty((Q.shape[0], 0))
    best_i = np.empty((Q.shape[0], 0), np.int64)
    for lo in range(0, X.shape[0], chunk):
        Xc = X[lo:lo + chunk].astype(np.float64)
        dc = (Xc ** 2).sum(1)[None, :] - 2.0 * Qd @ Xc.T + qn
        ic = np.broadcast_to(np.arange(lo, lo + Xc.shape[0]), dc.shape)
        d = np.concatenate([best_d, dc], 1)
        i = np.concatenate([best_i, ic], 1)
        part = np.argpartition(d, k - 1, axis=1)[:, :k]
        best_d = np.take_along_axis(d, part, 1)
        best_i = np.take_along_axis(i, part, 1)
    order = np.argsort(best_d, 1)
    return np.take_along_axis(best_i, order, 1), np.take_along_axis(best_d, order, 1)


def check_exact(name: str, ids, dists, X, Q, ref_i, ref_d) -> None:
    """Exact-rule answers against the float64 reference (module doc)."""
    ids = np.asarray(ids, np.int64)
    dists = np.asarray(dists, np.float64)
    require(ids.shape == ref_i.shape, f"{name}: ids shape {ids.shape}")
    require(((ids >= 0) & (ids < X.shape[0])).all(), f"{name}: invalid ids")
    require(all(len(set(r)) == len(r) for r in ids.tolist()),
            f"{name}: repeated ids within a query")
    rows = X[ids].astype(np.float64)                       # (nq, k, D)
    Qd = Q.astype(np.float64)[:, None, :]
    exact = ((rows - Qd) ** 2).sum(-1)                     # returned ids, f64
    tol = (F32_ULPS * np.finfo(np.float32).eps
           * ((rows ** 2).sum(-1) + (Qd ** 2).sum(-1)))
    require(np.isfinite(dists).all(), f"{name}: non-finite distances")
    err = np.abs(dists - exact)
    require((err <= tol).all(),
            f"{name}: distance off by {err.max():.3e} (> f32 tolerance)")
    swapped = ids != ref_i
    ties = np.abs(exact - ref_d) <= tol
    require(not (swapped & ~ties).any(),
            f"{name}: {int((swapped & ~ties).sum())} ids differ from the "
            "float64 reference beyond f32 distance ties")
    fact(f"{name} vs float64 reference",
         f"{ids.size - int(swapped.sum())}/{ids.size} ids equal, "
         f"{int(swapped.sum())} f32-resolution tie swaps, max |dist err| / "
         f"tolerance {float((err / tol).max()):.3f}")


def check_config(name: str, sess, **expect):
    cfg = sess.backend.engine_config(K)
    require(cfg.use_kernel is True, f"{name}: resolved use_kernel={cfg.use_kernel}")
    for key, val in expect.items():
        require(getattr(cfg, key) == val,
                f"{name}: resolved {key}={getattr(cfg, key)!r}, want {val!r}")
    fact(f"{name} engine", f"kind={cfg.kind} use_kernel={cfg.use_kernel} "
         f"d1={cfg.d1} row_block={cfg.row_block} dim_groups={cfg.dim_groups} "
         f"query_chunk={cfg.query_chunk}")


def device_memory(dev, label: str) -> None:
    stats = dev.memory_stats() or {}
    fact(f"{label} device memory",
         f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 'not reported')} "
         f"bytes_in_use={stats.get('bytes_in_use', 'not reported')}")


def certified_mask(res):
    mask = res.stats.extra.get("uncertified_mask")
    require(mask is not None, "no per-query certificate in the result")
    return ~np.asarray(mask, bool)


def load(scale: float, seed: int):
    t0 = time.perf_counter()
    ds = load_dataset("wikipedia", scale=scale, seed=seed)
    X, Q = ds.X, ds.Q[:N_QUERIES]
    fact("corpus", f"wikipedia family, {X.shape[0]} x {X.shape[1]} f32, "
         f"{Q.shape[0]} in-distribution queries, seed {seed}")
    fact("host data generation s", time.perf_counter() - t0)
    t0 = time.perf_counter()
    ref = reference_topk(X, Q, K)
    fact("host float64 reference s", time.perf_counter() - t0)
    return X, Q, ds.Q[N_QUERIES:], ref


def phase_serving(X, Q, ref, seed: int, clock: CompileClock, dev):
    """(a) the served search path; returns the fitted PDScanning+ method."""
    t0 = time.perf_counter()
    svc = open_index(X, index="flat", method="PDScanning+", backend="jax",
                     seed=seed, serving=True,
                     serving_params={"slots": SLOTS, "k": K})
    fact("(a) host fit s (PCA rotation)", time.perf_counter() - t0)
    t0 = time.perf_counter()
    check_config("(a) serving", svc.session, kind="lb", d1=128,
                 row_block=4096, dim_groups=1)
    fact("(a) corpus upload s", time.perf_counter() - t0)
    c0, n0 = clock.seconds, clock.compiles
    tickets = [svc.submit(q) for q in Q]
    svc.drain()
    statuses = Counter(t.status for t in tickets)
    fact("(a) ticket statuses", dict(statuses))
    require(statuses == Counter(done=len(Q)),
            f"(a) tickets not all done: {dict(statuses)}")
    require(all(t.certified is True for t in tickets),
            f"(a) {sum(t.certified is not True for t in tickets)} tickets "
            "not certified")
    walls = [tickets[i].service_s for i in range(0, len(Q), SLOTS)]
    fact("(a) compile s (jax.monitoring)", f"{clock.seconds - c0} "
         f"({clock.compiles - n0} backend compiles)")
    fact("(a) first batch wall s (includes compile)", walls[0])
    fact("(a) steady batch wall s", walls[1:])
    check_exact("(a) served tickets", np.stack([t.ids for t in tickets]),
                np.stack([t.dists for t in tickets]), X, Q, *ref)
    device_memory(dev, "(a)")
    return svc.session.method


def phase_kernels(X, Q, Q_train, ref, method, seed: int,
                  clock: CompileClock, dev):
    """(b) one PDX batch (dco_scan_grouped) and one DDCopq batch
    (pq_lookup), each in a fresh session."""
    sess = SearchSession(method, "flat", None, backend="jax",
                         policy=SchedulePolicy(dim_groups=4))
    check_config("(b) PDX", sess, kind="lb", dim_groups=4)
    c0 = clock.seconds
    res = sess.search(Q, K)
    fact("(b) PDX compile s", clock.seconds - c0)
    fact("(b) PDX batch wall s (includes compile)", res.wall_time_s)
    require(certified_mask(res).all(), "(b) PDX: uncertified queries")
    check_exact("(b) PDX batch", res.ids, res.dists, X, Q, *ref)
    device_memory(dev, "(b) PDX")
    del sess, res
    gc.collect()

    t0 = time.perf_counter()
    opq = make_method("DDCopq", seed=seed).fit(X)
    fact("(b) DDCopq host PQ fit s", time.perf_counter() - t0)
    t0 = time.perf_counter()
    opq.train(Q_train, K, candidates_per_query=X.shape[0])
    fact("(b) DDCopq host calibration s",
         f"{time.perf_counter() - t0} ({Q_train.shape[0]} held-out queries, "
         f"theta {opq.state['models'][K]})")
    sess = SearchSession(opq, "flat", None, backend="jax")
    check_config("(b) DDCopq", sess, kind="opq")
    c0 = clock.seconds
    res = sess.search(Q, K)
    fact("(b) DDCopq compile s", clock.seconds - c0)
    fact("(b) DDCopq batch wall s (includes compile)", res.wall_time_s)
    rec = recall_at_k(res.ids, ref[0])
    fact("(b) DDCopq recall@10", rec)
    require(rec >= OPQ_RECALL, f"(b) DDCopq recall@10 {rec} < {OPQ_RECALL}")
    device_memory(dev, "(b) DDCopq")


def phase_mesh(X, Q, ref, seed: int, clock: CompileClock, n_chips: int):
    """The mesh-sharded flat corpus over ``n_chips`` devices."""
    mesh = make_host_mesh(n_chips, 1)
    t0 = time.perf_counter()
    sess = open_index(X, index="flat", method="PDScanning+", backend="jax",
                      seed=seed, mesh=mesh)
    fact("mesh host fit s (PCA rotation)", time.perf_counter() - t0)
    check_config("mesh", sess, kind="lb", d1=128)
    c0 = clock.seconds
    res = sess.search(Q, K)
    fact("mesh compile s", clock.seconds - c0)
    fact("mesh first batch wall s (includes compile)", res.wall_time_s)
    fact("mesh steady batch wall s", sess.search(Q, K).wall_time_s)
    # every array of the per-shard layout must be split over all devices,
    # not parked on one: the same number of row blocks on each
    layout = sess.backend._blocks
    nb = layout["xl"].shape[0] // n_chips
    for key, a in layout.items():
        blocks = Counter()
        for s in a.addressable_shards:
            blocks[s.device.id] += s.data.shape[0]
        require(len(blocks) == n_chips and set(blocks.values()) == {nb},
                f"mesh: layout array {key} {a.shape} placed as "
                f"{dict(blocks)}")
    fact("mesh placement", f"{len(layout)} layout arrays, each {nb} blocks "
         f"of {layout['xl'].shape[1]} rows on each of {n_chips} devices")
    require(certified_mask(res).all(), "mesh: uncertified queries")
    check_exact("mesh batch", res.ids, res.dists, X, Q, *ref)
    for d in mesh.devices.flat:
        device_memory(d, f"mesh device {d.id}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: no TPU found (JAX default backend is "
              f"{jax.default_backend()!r}); nothing was run", file=sys.stderr)
        return 2
    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2
    fact("device", f"{devices[0].platform} {devices[0].device_kind} "
         f"x{len(devices)} (using {args.chips})")
    fact("compile cache", enable_compile_cache())
    clock = CompileClock()
    try:
        if args.chips == 1:
            X, Q, Q_train, ref = load(SCALE, args.seed)
            method = phase_serving(X, Q, ref, args.seed, clock, devices[0])
            gc.collect()        # release (a)'s device corpus before (b)
            phase_kernels(X, Q, Q_train, ref, method, args.seed, clock,
                          devices[0])
        else:
            X, Q, _, ref = load(SCALE * args.chips, args.seed)
            phase_mesh(X, Q, ref, args.seed, clock, args.chips)
    except SmokeFailure as exc:
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
