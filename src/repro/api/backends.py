"""Backend executors behind ``SearchSession``.

``HostBackend`` runs the staged numpy scan (core.engine.scan_topk) over a
flat corpus, an IVF partition probe, or an HNSW graph walk.  ``JaxBackend``
runs the device engines over a flat corpus — the streaming block-fused scan
(core.stream_engine, default) or the legacy two-stage engine
(core.jax_engine) — single device or, when a mesh is supplied, sharded with
a global top-k merge.  A flat corpus can also be probed IVF-style on device:
rows are laid out partition-major and the streaming engine masks/skips
unprobed partitions.  Both backends consume the SAME fitted method state:
the host path via ``method.screen``/``exact_sq``, the device path via the
method's uniform ``device_state()`` export.
"""
from __future__ import annotations

import time

import numpy as np

from repro.core.engine import (EXTRA_COVERAGE, EXTRA_DIMS_READ_MEAN,
                               EXTRA_EST_SAVED_FLOPS, EXTRA_FALLBACK_BLOCKS,
                               EXTRA_RULE_TIMELINE, EXTRA_SCREEN_PASS_MEAN,
                               EXTRA_SHARED_BLOCKS, EXTRA_SURVIVORS_MEAN, EXTRA_UNCERTIFIED_MASK,
                               EXTRA_UNCERTIFIED_QUERIES, QueryBatch,
                               ScanStats, scan_topk)
from repro.core.policy import PolicyConfig, finalize_adaptive_extra
from repro.testing import faults
from repro.utils.spans import span


def _arm_guardrail(method, index_kind: str, policy, backend: str):
    """Build the per-(method, backend) breaker when the schedule asks for
    one (DESIGN.md §9).  HNSW walks have no scan-shaped certified fallback
    to demote to (rejected); ``FDScanning`` already IS the certified full
    scan, so there is nothing to guard (silently unarmed — documented in
    docs/methods.md)."""
    gcfg = getattr(policy, "guardrails", None)
    if gcfg is None or gcfg is False:
        return None
    if index_kind == "hnsw":
        raise ValueError(
            "guardrails demote scan-shaped searches (index='flat'/'ivf') to "
            "a certified full scan; an HNSW graph walk has no such fallback "
            "(DESIGN.md §9)")
    if method.name == "FDScanning":
        return None
    from repro.core.guardrails import Guardrail, GuardrailConfig
    if gcfg is True:
        gcfg = GuardrailConfig()
    return Guardrail(gcfg, method, backend)


class HostBackend:
    """Numpy staged-scan execution over flat / IVF / HNSW candidates."""

    name = "host"

    def __init__(self, method, index_kind: str, index, policy):
        self.method = method
        self.index_kind = index_kind
        self.index = index
        self.policy = policy
        # adaptive fdscan fallback (DESIGN.md §5) for the scan-shaped index
        # kinds; HNSW graph walks screen tiny per-hop batches with a
        # different cost structure and ignore it
        self._pol = PolicyConfig.from_schedule(policy)
        # demoted serving: every candidate block completes exactly
        self._pol_demoted = PolicyConfig(adaptive=True, force_fallback=True)
        self.guardrail = _arm_guardrail(method, index_kind, policy, "host")

    def invalidate(self):
        """No-op: nothing is cached on the host path."""
        pass

    def notify_append(self, n_new: int, parts=None) -> str:
        """Inserts need no layout work on the host path (the scan reads the
        method's live numpy arrays); returns the write mode for telemetry
        parity with the jax backend."""
        return "noop"

    def search(self, Q, k: int, *, nprobe: int, ef: int,
               deadline_s: float | None = None):
        """Batched staged-scan top-k; returns (dists, ids, stats).

        ``deadline_s`` (seconds of wall budget for the whole batch) arms
        anytime mode (DESIGN.md §7): the scan checks the clock between
        candidate blocks, queries past the budget return their running
        top-k, and per-query ``coverage`` (candidate blocks scanned, 1.0 =
        complete) lands in ``stats.extra`` with partial queries flagged in
        ``uncertified_mask``.

        With ``SchedulePolicy(guardrails=...)`` armed, non-deadline batches
        route through the breaker (DESIGN.md §9): drift is scored, a
        sampled audit shadow-runs the certified path, and an OPEN breaker
        serves the whole batch by the exhaustive certified scan.  Deadline
        calls bypass the guardrail (anytime partials are already flagged
        uncertified and must stay deterministic)."""
        faults.check_search(faults.active(self.policy))
        g = self.guardrail
        if g is not None and deadline_s is None:
            return g.run(
                Q, k,
                screen=lambda q: self._search(q, k, nprobe=nprobe, ef=ef),
                certified=lambda q: self._search(q, k, nprobe=nprobe, ef=ef,
                                                 demoted=True),
                plan=faults.active(self.policy))
        return self._search(Q, k, nprobe=nprobe, ef=ef,
                            deadline_s=deadline_s)

    def _search(self, Q, k: int, *, nprobe: int, ef: int,
                deadline_s: float | None = None, demoted: bool = False):
        """The scan itself; ``demoted=True`` serves every candidate block
        by the exhaustive exact completion (``PolicyConfig(force_fallback)``
        pins the host policy's fallback mode — the guardrail's certified
        reference/serving path)."""
        m = self.method
        t_end = None
        if deadline_s is not None:
            if self.index_kind == "hnsw":
                raise ValueError(
                    "anytime deadlines interrupt scan-shaped searches "
                    "(index='flat'/'ivf'); an HNSW graph walk has no block "
                    "boundary to stop at (DESIGN.md §7)")
            t_end = time.monotonic() + float(deadline_s)
        pol = self._pol_demoted if demoted else self._pol
        batch = QueryBatch.create(m, Q, self.policy.stage_dims(m.state["D"]))
        dists = np.empty((len(batch), k), np.float32)
        ids = np.empty((len(batch), k), np.int64)
        all_ids = None
        for qi in range(len(batch)):
            if self.index_kind == "flat":
                if all_ids is None:
                    all_ids = np.arange(m.state["N"])
                d, i = scan_topk(m, batch, qi, all_ids, k, policy=pol,
                                 deadline_ts=t_end)
            elif self.index_kind == "ivf":
                d, i = self.index.search(m, batch, qi, k, nprobe,
                                         policy=pol, deadline_ts=t_end)
            else:                   # hnsw
                d, i = self.index.search(m, batch, qi, k, max(ef, k))
            n = min(k, len(d))
            dists[qi, :n], ids[qi, :n] = d[:n], i[:n]
            if n < k:
                dists[qi, n:], ids[qi, n:] = np.inf, -1
        self._finalize_stats(batch.stats, len(batch))
        return dists, ids, batch.stats

    @staticmethod
    def _finalize_stats(stats, nq: int) -> None:
        """Fold scan accumulators into the canonical ``extra`` telemetry
        keys (api.types.STAT_EXTRA_KEYS) so host batches report the same
        fields as the jax backend."""
        completed = stats.extra.pop("_completed_total", None)
        if completed is not None:
            # no completion budget on the host scan: pass == completed
            stats.extra[EXTRA_SURVIVORS_MEAN] = completed / max(nq, 1)
            stats.extra[EXTRA_SCREEN_PASS_MEAN] = completed / max(nq, 1)
        # every host survivor is exactly completed -> certified, UNLESS an
        # anytime deadline cut the scan short: unscanned candidate blocks
        # may hold true neighbors, so partial queries are uncertified
        cov = stats.extra.pop("_coverage", None)
        coverage = np.ones(nq, np.float32)
        if cov is not None:
            coverage[:len(cov)] = np.asarray(cov, np.float32)
        stats.extra[EXTRA_COVERAGE] = coverage
        stats.extra[EXTRA_UNCERTIFIED_MASK] = coverage < 1.0
        stats.extra[EXTRA_UNCERTIFIED_QUERIES] = float(
            (coverage < 1.0).mean())
        stats.extra[EXTRA_DIMS_READ_MEAN] = (
            stats.dims_scanned / max(stats.n_dco, 1))
        finalize_adaptive_extra(stats)


class JaxBackend:
    """Device engines over a flat or IVF-probed corpus (flat optionally
    mesh-sharded).

    Lazily materializes the dimension-blocked device arrays from
    ``method.device_state()`` and rebuilds them after ``invalidate()``.
    Dynamic inserts take the LSM-style write path (DESIGN.md §6): the
    session's ``add`` calls ``notify_append``, which keeps the cached main
    block layout and serves the new rows from a small delta segment scanned
    alongside it (one running tau across both segments), re-materializing
    only once the delta exceeds ``SchedulePolicy.delta_merge_threshold``
    rows.  Query padding to the chunk size is handled inside the engines, so
    ragged batches are fine.
    """

    name = "jax"

    def __init__(self, method, index_kind: str, index, policy, *, mesh=None):
        if index_kind not in ("flat", "ivf"):
            raise ValueError(
                f"backend='jax' serves index='flat' or 'ivf' (got "
                f"{index_kind!r}); HNSW graph walks are host-side indexes")
        if index_kind == "ivf" and mesh is not None:
            raise ValueError(
                "device IVF probing is single-device; mesh-shard a flat "
                "corpus instead")
        if mesh is not None and getattr(policy, "adaptive", False):
            raise ValueError(
                "the adaptive DCO policy is single-device for now — drop "
                "SchedulePolicy(adaptive=True) on the mesh path "
                "(DESIGN.md §5)")
        if mesh is not None and getattr(policy, "guardrails", None) is not None:
            raise ValueError(
                "guardrails are single-device (the breaker's demotion runs "
                "the streaming engine's forced full-scan body) — drop "
                "SchedulePolicy(guardrails=...) on the mesh path "
                "(DESIGN.md §9)")
        self.method = method
        self.index_kind = index_kind
        self.index = index
        self.policy = policy
        self.mesh = mesh
        self._dstate = None         # host-side device_state() export
        self._state = None          # jnp arrays (single-device path)
        self._blocks = None         # cached stream-engine corpus layout
        self._groups = 1            # resolved PDX dim groups of that layout
        self._shard_args = None     # device_put shards (mesh path)
        self._mesh_fns: dict = {}   # cfg -> shard_map fn
        self._mesh_row_block = None  # shard-aligned row_block (mesh path)
        self._list_sizes = None     # IVF partition sizes (probe stats)
        self._cfg_cache: dict = {}  # (k, anytime, demoted) -> DcoEngineConfig
                                    # (same object per call so jit static-arg
                                    # caching stays on the identity fast path)
        self.guardrail = _arm_guardrail(method, index_kind, policy, "jax")
        # ---- LSM-style delta segment (DESIGN.md §6) ----
        self._n_main = 0            # rows in the materialized main layout
        self._delta_parts = np.empty(0, np.int32)   # IVF parts of delta rows
        self._delta_blocks = None   # cached combined main+delta layout
        self._delta_tail_min = np.inf
        self._delta_dirty = False
        # write-path telemetry (bench_serving's insert amplification)
        self.rows_inserted = 0      # rows arriving through notify_append
        self.rows_written = 0       # rows laid out on device (full + delta)
        self.merges = 0             # threshold-triggered re-materializations

    # -- state management ---------------------------------------------------
    def invalidate(self):
        """Drop materialized device arrays (full re-materialization on the
        next search; ``notify_append`` is the cheaper delta path for adds)."""
        self._dstate = self._state = self._blocks = self._shard_args = None
        self._groups = 1
        self._list_sizes = None
        self._mesh_fns.clear()
        self._cfg_cache.clear()
        self._n_main = 0
        self._delta_parts = np.empty(0, np.int32)
        self._delta_blocks = None
        self._delta_tail_min = np.inf
        self._delta_dirty = False

    def _resolved_engine(self) -> str:
        """The engine ``search`` will actually run (opq / IVF probing / the
        adaptive policy / guardrail demotion are stream-only); requires a
        materialized _dstate."""
        if (self._dstate["kind"] == "opq" or self.index_kind == "ivf"
                or PolicyConfig.from_schedule(self.policy) is not None
                or self.guardrail is not None):
            return "stream"
        return self.policy.engine

    @property
    def delta_rows(self) -> int:
        """Rows currently served from the delta segment (0 when merged)."""
        if self._dstate is None:
            return 0
        return int(self.method.state["N"]) - self._n_main

    def notify_append(self, n_new: int, parts=None) -> str:
        """Register ``n_new`` rows just appended to the method state.

        Returns the write mode taken:
          ``"delta"``    rows join the delta segment; the cached main block
                         layout survives and the next search scans both
                         segments under one running tau;
          ``"merge"``    the delta exceeded ``delta_merge_threshold`` — the
                         whole layout re-materializes on the next search;
          ``"rebuild"``  delta path unavailable (mesh / two_stage engine /
                         threshold 0): legacy full invalidation;
          ``"cold"``     nothing was materialized yet, so the first search
                         lays out everything at once anyway.
        ``parts`` is the IVF partition assignment of the new rows (required
        for index_kind='ivf'; IVFIndex.insert returns it)."""
        self.rows_inserted += int(n_new)
        if self._dstate is None:
            self.invalidate()
            return "cold"
        thresh = self.policy.delta_merge_threshold
        if self.mesh is not None or thresh <= 0 \
                or self._resolved_engine() != "stream":
            self.invalidate()
            return "rebuild"
        if self.index_kind == "ivf":
            if parts is None:
                raise ValueError("notify_append(index='ivf') needs the "
                                 "partition assignment of the new rows")
            self._delta_parts = np.concatenate(
                [self._delta_parts, np.asarray(parts, np.int32)])
        if self.delta_rows > thresh:
            self.merges += 1
            self.invalidate()
            return "merge"
        self._delta_dirty = True
        return "delta"

    def _build_delta(self):
        """(Re)build the delta segment's blocks at the main layout's width
        and concatenate them after the cached main blocks — the LSM write
        path.  Host work is O(delta) (no transform recompute: methods keep
        Xrot incrementally); the device-side concat copies the main blocks
        (O(N) bandwidth) but never retraces or re-materializes them."""
        import jax.numpy as jnp
        from repro.core.stream_engine import append_stream_blocks

        n_total = int(self.method.state["N"])
        n_delta = n_total - self._n_main
        ds = self.method.device_state()
        if ds["kind"] != self._dstate["kind"]:
            # the method was re-trained under us (kind flip, e.g. DDCopq
            # lb->opq): the cached main layout is for the wrong rule
            self.invalidate()
            self._materialize()
            return self._blocks
        xr = np.asarray(ds["Xrot"], np.float32)[self._n_main:]
        d1 = self._d1
        # quantize the segment to whole blocks HOST-side (same pad rows the
        # device build would add: zeros with id -1) so every delta size
        # within the same block count shares one build/scan trace — without
        # this, each insert changes the input shapes and retraces the jitted
        # build, turning the first post-insert search into a compile stall
        B = int(self._blocks["xl"].shape[-2])
        pad = -n_delta % B
        self._delta_tail_min = float((xr[:, d1:] ** 2).sum(1).min())
        row_ids = np.arange(self._n_main, n_total, dtype=np.int32)
        parts = np.asarray(self._delta_parts, np.int32)
        codes = (np.asarray(ds["codes"], np.int32)[self._n_main:]
                 if ds["kind"] == "opq" else None)
        if pad:
            xr = np.concatenate([xr, np.zeros((pad, xr.shape[1]),
                                              np.float32)])
            row_ids = np.concatenate([row_ids, np.full(pad, -1, np.int32)])
            if parts.size:      # edge-mode, as build_stream_blocks pads
                parts = np.concatenate([parts, np.full(pad, parts[-1],
                                                       np.int32)])
            if codes is not None:
                codes = np.concatenate(
                    [codes, np.zeros((pad, codes.shape[1]), np.int32)])
        dstate = {
            "x_lead": xr[:, :d1], "x_tail": xr[:, d1:],
            "lead_sq": (xr[:, :d1] ** 2).sum(1),
            "tail_sq": (xr[:, d1:] ** 2).sum(1),
            "row_ids": jnp.asarray(row_ids),
        }
        if self.index_kind == "ivf":
            dstate["row_part"] = jnp.asarray(parts)
        if codes is not None:
            dstate["codes"] = jnp.asarray(codes)
        self._delta_blocks = append_stream_blocks(self._blocks, dstate)
        self._delta_dirty = False
        self.rows_written += n_delta
        return self._delta_blocks

    def _materialize(self):
        import jax.numpy as jnp
        from repro.core.jax_engine import build_device_state, rule_scalars

        dstate = self.method.device_state()
        if self.mesh is not None and dstate["kind"] == "opq":
            # PQ screening is single-device for now; mesh shards fall back to
            # the exact lower-bound rule of the base export (same fallback
            # untrained DDCopq uses)
            from repro.core.methods import DCOMethod
            dstate = DCOMethod.device_state(self.method)
        xr = np.asarray(dstate["Xrot"], np.float32)
        D = self.method.state["D"]
        if xr.shape[1] != D:
            raise ValueError(
                f"{self.method.name}: rotation rank {xr.shape[1]} < D={D}; "
                "the device engine needs a full-rank rotation for exact "
                "stage-2 completion — use backend='host' at this D")
        extra = {}
        if self.index_kind == "ivf":
            # partition-major layout: the streaming engine probes by masking
            # row blocks whose partition span was not selected
            part = np.empty(self.method.state["N"], np.int64)
            for j, lst in enumerate(self.index.lists):
                part[lst] = j
            perm = np.argsort(part, kind="stable")
            xr = xr[perm]
            dstate = dict(dstate, Xrot=xr)
            extra["row_ids"] = jnp.asarray(perm, jnp.int32)
            extra["row_part"] = jnp.asarray(part[perm], jnp.int32)
            self._list_sizes = np.array([len(lst) for lst in self.index.lists])
        if dstate["kind"] == "opq":
            codes = np.asarray(dstate["codes"])
            if self.index_kind == "ivf":
                codes = codes[perm]
            extra["codes"] = jnp.asarray(codes, jnp.int32)
        self._dstate = dstate
        self._d1 = min(self.policy.d1, D)
        # PDX vertical layout (DESIGN.md §8): resolve the dim-group count the
        # streaming scan will run with, so the cached blocks, the engine
        # config and the delta segment all share ONE layout.  Forced to 1 off
        # the stream engine and for rules with no partial-distance screen
        # (the same cases stream_engine._effective_groups collapses).
        self._groups = 1
        if (self.mesh is None and self._resolved_engine() == "stream"
                and dstate["kind"] not in ("fdscan", "opq")):
            self._groups = max(1, int(self.policy.dim_groups))
        self._n_main = int(self.method.state["N"])
        self.rows_written += self._n_main
        if self.mesh is None:
            self._state = build_device_state(dstate, self._d1)
            self._state.update(extra)
        else:
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            sh = NamedSharding(self.mesh, P(tuple(self.mesh.axis_names)))
            d1 = self._d1
            self._shard_args = tuple(
                jax.device_put(v, sh)
                for v in (xr[:, :d1], xr[:, d1:],
                          (xr[:, :d1] ** 2).sum(1), (xr[:, d1:] ** 2).sum(1)))
            self._mesh_extra_state = rule_scalars(dstate, d1)
            # certificate sharp edge (make_distributed_topk): a shard whose
            # row count is not a row_block multiple pads phantom rows inside
            # the compiled call, weakening the per-shard certificate — so
            # align row_block to the largest divisor of the shard size
            # (facade sessions never hit the build-time error)
            from repro.core.jax_engine import _aligned_row_block
            n_shards = int(np.prod(tuple(self.mesh.shape.values())))
            per_shard = max(1, self._n_main // max(n_shards, 1))
            self._mesh_row_block = _aligned_row_block(
                per_shard, self.policy.row_block)

    def engine_config(self, k: int):
        """The resolved ``DcoEngineConfig`` that ``search(Q, k)`` runs
        (``use_kernel`` decided, PDX groups and mesh row block applied);
        materializes the device state on first use."""
        if self._dstate is None:
            self._materialize()
        return self._config(k)

    def _config(self, k: int, anytime: bool = False, demoted: bool = False):
        from repro.core.jax_engine import DcoEngineConfig

        if (k, anytime, demoted) in self._cfg_cache:
            return self._cfg_cache[(k, anytime, demoted)]
        ds, p = self._dstate, self.policy
        row_block = p.row_block if self.mesh is None \
            else getattr(self, "_mesh_row_block", p.row_block)
        kw = dict(kind=ds["kind"], d1=self._d1, k=k, capacity=p.capacity,
                  query_chunk=p.query_chunk, tau_slack=p.tau_slack,
                  row_block=row_block, block_capacity=p.block_capacity,
                  use_kernel=p.use_kernel, dim_groups=self._groups,
                  group_capacity=p.group_capacity)
        if ds["kind"] == "adsampling":
            kw["eps0"] = float(ds.get("eps0", 2.1))
        elif ds["kind"] == "ddcres":
            kw["m"] = float(ds.get("m", 3.0))
        elif ds["kind"] == "ratio":
            kw["theta"] = self._ratio_theta(k)
        elif ds["kind"] == "opq":
            kw["theta"] = float(ds["theta"])
        # fdscan has nothing to fall back to; anytime deadline calls run the
        # fixed resumable scan (DESIGN.md §7), so they strip the policy too.
        # A demoted config (guardrail breaker OPEN / audit reference,
        # DESIGN.md §9) pins force_fallback: every chunk runs the certified
        # full-scan body regardless of what the schedule says.
        if demoted:
            kw["policy"] = PolicyConfig(adaptive=True, force_fallback=True,
                                        fallback_margin=p.fallback_margin)
        elif ds["kind"] != "fdscan" and not anytime:
            kw["policy"] = PolicyConfig.from_schedule(p)
        # resolve use_kernel HERE so the cached config is final: an
        # unresolved None makes stream_topk dataclasses.replace() a fresh
        # static arg every call, pushing jit dispatch onto the slow path
        if kw.get("policy") is not None and ds["kind"] != "opq":
            kw["use_kernel"] = False    # see stream_topk: adaptive forces
                                        # the jnp dco_scan path (pq_lookup
                                        # keeps its kernel)
        elif kw["use_kernel"] is None:
            from repro.kernels.ops import _on_tpu
            kw["use_kernel"] = _on_tpu()
        cfg = DcoEngineConfig(**kw)
        self._cfg_cache[(k, anytime, demoted)] = cfg
        return cfg

    def _ratio_theta(self, k: int) -> float:
        """Largest trained stage <= d1 for the trained k; theta=1.0 (exact
        lower-bound rule) when no model applies."""
        models = self._dstate.get("models") or {}
        trained = [(d, th) for (kk, d), th in models.items()
                   if kk == self._dstate.get("trained_k") and d <= self._d1]
        return max(trained)[1] if trained else 1.0

    def _prep_queries(self, Q):
        """Rotate/center queries into the device basis + per-query extras:
        DDCres scalars (tail query energy and Eq. 6 variance suffix at d1)
        or the DDCopq PQ lookup tables."""
        ds, d1 = self._dstate, self._d1
        Q = np.atleast_2d(np.asarray(Q, np.float32))
        Qp = Q - ds["mean"] if ds.get("mean") is not None else Q
        Qr = Qp @ ds["W"] if ds.get("W") is not None else Qp
        q_extra = {}
        if ds["kind"] == "ddcres":
            qres = np.clip((Qp ** 2).sum(1) - (Qr ** 2).sum(1), 0.0, None)
            var = ((Qr[:, d1:] ** 2) * ds["sigma_sq"][None, d1:]).sum(1)
            q_extra = {
                "qtail_sq": (Qr[:, d1:] ** 2).sum(1) + qres,
                "var_d1": var + qres * float(ds["tail_var"]),
            }
        elif ds["kind"] == "opq":
            from repro.core import transforms as T
            pq = {"books": ds["books"], "splits": ds["splits"]}
            q_extra = {"lut": np.stack([T.pq_query_lut(pq, q) for q in Qr])}
        return Qr[:, :d1], Qr[:, d1:], q_extra

    def _probe(self, Q, nprobe: int):
        """Rank partitions by centroid distance (same rule as the host
        IVFIndex.probe_ids) -> (nq, nprobe) partition ids + candidate counts."""
        cent = self.index.centroids
        npb = min(nprobe, cent.shape[0])
        Q = np.atleast_2d(np.asarray(Q, np.float32))
        d2 = (cent ** 2).sum(1)[None, :] - 2.0 * Q @ cent.T   # +||q||^2 const
        probed = np.argpartition(d2, npb - 1, axis=1)[:, :npb]
        return probed.astype(np.int32), self._list_sizes[probed].sum(1)

    # -- search --------------------------------------------------------------
    def search(self, Q, k: int, *, nprobe: int, ef: int,
               deadline_s: float | None = None):
        """Batched device top-k; returns (dists, ids, stats).  ``ef`` is
        accepted for signature parity with the host backend (unused).

        ``deadline_s`` (seconds of wall budget for the whole batch) arms the
        streaming engine's anytime mode (DESIGN.md §7): the corpus is walked
        in block groups with a wall check at each boundary, an expired
        budget returns the running top-k, and the scanned fraction lands in
        ``stats.extra["coverage"]`` with partial queries flagged
        uncertified.  Single-device stream engine only (the adaptive policy
        is stripped for the deadline call; mesh raises).

        With ``SchedulePolicy(guardrails=...)`` armed, non-deadline batches
        route through the breaker (DESIGN.md §9): drift is scored, a
        sampled audit shadow-runs the certified forced full scan, and an
        OPEN breaker serves the whole batch through it.  Deadline calls
        bypass the guardrail (anytime partials are already flagged
        uncertified and must stay deterministic)."""
        faults.check_search(faults.active(self.policy))
        g = self.guardrail
        if g is not None and deadline_s is None:
            return g.run(
                Q, k,
                screen=lambda q: self._search(q, k, nprobe=nprobe, ef=ef),
                certified=lambda q: self._search(q, k, nprobe=nprobe, ef=ef,
                                                 demoted=True),
                plan=faults.active(self.policy))
        return self._search(Q, k, nprobe=nprobe, ef=ef,
                            deadline_s=deadline_s)

    def _search(self, Q, k: int, *, nprobe: int, ef: int,
                deadline_s: float | None = None, demoted: bool = False):
        """The engine dispatch itself; ``demoted=True`` swaps in the
        forced-fallback config (every chunk runs the certified full-scan
        body — the guardrail's reference/serving path, DESIGN.md §9)."""
        import jax
        import jax.numpy as jnp
        from repro.core.jax_engine import make_distributed_topk, two_stage_topk
        from repro.core.stream_engine import stream_topk

        if self._dstate is None:
            self._materialize()
        with span("search.prep"):
            t_end = None
            if deadline_s is not None:
                if self.mesh is not None:
                    raise ValueError(
                        "anytime deadlines are single-device (the mesh scan "
                        "has no per-group host sync to check the clock at; "
                        "DESIGN.md §7)")
                t_end = time.monotonic() + float(deadline_s)
            cfg = self._config(k, anytime=t_end is not None, demoted=demoted)
            ql, qt, qe = self._prep_queries(Q)
            qe = {key: jnp.asarray(v) for key, v in qe.items()}
            ql, qt = jnp.asarray(ql), jnp.asarray(qt)
        nq, N, D = ql.shape[0], self.method.state["N"], self.method.state["D"]
        engine = self.policy.engine
        if (cfg.kind == "opq" or self.index_kind == "ivf"
                or cfg.policy is not None or t_end is not None):
            engine = "stream"       # only the streaming engine serves these
        cand_per_q = np.full(nq, N, np.float64)
        passed = dmin = report = coverage = dims_read = shared = None
        n_anchor = 0                # two_stage completes k anchors per query
        if self.mesh is None:
            if engine == "two_stage":
                out = two_stage_topk(self._state, ql, qt, cfg, qe)
                n_anchor = nq * k
            else:
                from repro.core.stream_engine import build_stream_blocks
                if self._blocks is None:
                    # pad+reshape of the whole corpus happens once per
                    # materialization, not per query batch
                    self._blocks = build_stream_blocks(
                        self._state, self.policy.row_block,
                        dim_groups=self._groups)
                blocks, st = self._blocks, self._state
                if self.delta_rows:
                    if self._delta_dirty or self._delta_blocks is None:
                        self._build_delta()
                    blocks = self._delta_blocks
                    # thread the combined tail-norm min so the ddcres screen
                    # stays as loose as fitted (stream_engine tail_min)
                    st = dict(self._state, tail_min=jnp.minimum(
                        self._state["tail_sq"].min(),
                        jnp.float32(self._delta_tail_min)))
                probe = None
                if self.index_kind == "ivf":
                    probed, cand_per_q = self._probe(Q, nprobe)
                    probe = jnp.asarray(probed)
                    nd = self.delta_rows
                    if nd:
                        # delta rows are probe candidates too when their
                        # partition was selected
                        cand_per_q = cand_per_q + (
                            self._delta_parts[None, :nd, None]
                            == probed[:, None, :]).any(-1).sum(1)
                out = stream_topk(
                    st, ql, qt, cfg, qe, probe, blocks=blocks,
                    deadline_ts=t_end,
                    block_group=self.policy.anytime_block_group)
            # one batched transfer: the post-jit slices (and the adaptive
            # report) are tiny lazy dispatches — converting them one
            # np.asarray at a time serializes a sync per output
            with span("search.fetch"):
                out = jax.device_get(out)
            if engine == "two_stage":
                d, i, surv = out
            elif cfg.policy is not None:
                d, i, surv, passed, dmin, dims_read, shared, report = out
            elif t_end is not None:
                d, i, surv, passed, dmin, dims_read, shared, coverage = out
            else:
                d, i, surv, passed, dmin, dims_read, shared = out
            if shared is not None:
                # blocks each query's chunk completed chunk-shared, over
                # the blocks it scanned
                shared = float(shared.mean()) / (
                    blocks["xl"].shape[0] * (coverage or 1.0))
            if coverage is not None:
                # partial scans only touched this fraction of the corpus:
                # charge candidate work pro rata so pruning stats stay honest
                cand_per_q = cand_per_q * coverage
        else:
            if cfg not in self._mesh_fns:
                self._mesh_fns[cfg] = jax.jit(
                    make_distributed_topk(self.mesh, cfg,
                                          tuple(self.mesh.axis_names),
                                          extra_state=self._mesh_extra_state,
                                          engine=engine,
                                          n_rows=self._n_main))
            d, i, surv, dmin = self._mesh_fns[cfg](*self._shard_args,
                                                   ql, qt, qe)
            with span("search.fetch"):
                surv = np.asarray(surv)     # real completions, psum'd
                jax.block_until_ready(d)
            if engine == "two_stage":
                n_anchor = nq * k * int(np.prod(tuple(self.mesh.shape.values())))
        with span("search.finish") as sp:
            stats = ScanStats(n_dco=int(cand_per_q.sum()),
                              dims_total=float((cand_per_q * D).sum()))
            if cfg.kind == "fdscan":
                stats.dims_scanned = stats.dims_total
            elif cfg.kind == "opq":
                # PQ screening charges n_sub 'dims' per candidate (as the host
                # rule does); survivors complete the full D original dims
                n_sub = int(self._dstate["books"].shape[0])
                stats.dims_scanned = (float((cand_per_q * n_sub).sum())
                                      + float(surv.sum()) * D)
                stats.extra[EXTRA_SURVIVORS_MEAN] = float(surv.mean())
                stats.extra[EXTRA_SCREEN_PASS_MEAN] = float(np.asarray(passed).mean())
                self._certify(stats, d, dmin)
            else:
                # stage 1 streams d1 dims for every candidate row; stage 2 (plus
                # the two-stage engine's k anchor completions) streams the tail
                # for the ACTUAL survivors
                stats.dims_scanned = (float((cand_per_q * self._d1).sum())
                                      + float(surv.sum() + n_anchor) * (D - self._d1))
                stats.extra[EXTRA_SURVIVORS_MEAN] = float(surv.mean())
                if passed is not None:
                    stats.extra[EXTRA_SCREEN_PASS_MEAN] = float(np.asarray(passed).mean())
                self._certify(stats, d, dmin)
            if dims_read is not None:
                # the streaming scan measured its own reads (per-group alive
                # counts + completed tails, DESIGN.md §8): trust them over the
                # stage-shaped formula — under PDX early exit the formula
                # overstates lead reads, under adaptive fallback it understates
                stats.dims_scanned = float(
                    np.asarray(dims_read, np.float64).sum())
            stats.extra[EXTRA_DIMS_READ_MEAN] = (
                stats.dims_scanned / max(stats.n_dco, 1))
            if shared is not None:
                stats.extra[EXTRA_SHARED_BLOCKS] = shared
                sp.set_metadata(shared_block_share=shared)
            if report is not None:
                stats.extra[EXTRA_FALLBACK_BLOCKS] = float(
                    np.asarray(report["fallback_blocks"]).mean())
                stats.extra[EXTRA_EST_SAVED_FLOPS] = float(
                    np.asarray(report["est_saved_flops"]).sum())
                stats.extra[EXTRA_RULE_TIMELINE] = [
                    float(v) for v in np.asarray(report["rule_timeline"])]
            # anytime coverage (DESIGN.md §7): every query of the batch shares
            # the scanned-block fraction; partial scans are uncertified even if
            # the dropped-estimate certificate held over the scanned prefix
            cov_arr = np.full(nq, 1.0 if coverage is None else coverage,
                              np.float32)
            stats.extra[EXTRA_COVERAGE] = cov_arr
            mask = stats.extra.get(EXTRA_UNCERTIFIED_MASK)
            if mask is not None and coverage is not None and coverage < 1.0:
                stats.extra[EXTRA_UNCERTIFIED_MASK] = mask | (cov_arr < 1.0)
                stats.extra[EXTRA_UNCERTIFIED_QUERIES] = float(
                    stats.extra[EXTRA_UNCERTIFIED_MASK].mean())
            return (np.asarray(d, np.float32), np.asarray(i, np.int64), stats)

    @staticmethod
    def _certify(stats, d, dmin):
        """Streaming-engine exactness certificate: a query is certified iff
        every estimate the per-block completion budget dropped exceeds its
        returned k-th distance (so no true neighbor can have been truncated;
        DESIGN.md §4).  For estimator rules the stat is advisory."""
        if dmin is None:
            return
        fail = np.asarray(dmin) <= np.asarray(d)[:, -1]
        stats.extra[EXTRA_UNCERTIFIED_QUERIES] = float(fail.mean())
        stats.extra[EXTRA_UNCERTIFIED_MASK] = fail


def make_backend(name: str, method, index_kind: str, index, policy, *, mesh=None):
    """Construct the executor for ``name`` ('host' or 'jax')."""
    if name == "host":
        if mesh is not None:
            raise ValueError("mesh sharding is a jax-backend feature")
        return HostBackend(method, index_kind, index, policy)
    if name == "jax":
        return JaxBackend(method, index_kind, index, policy, mesh=mesh)
    raise ValueError(f"unknown backend {name!r} (expected 'host' or 'jax')")
