"""The facade: ``open_index(...)`` -> ``SearchSession``.

One entrypoint owns the whole lifecycle the paper's comparison needs —
method fitting/training, index construction, backend dispatch — so swapping
a DCO method, an index, or the host/device backend is a keyword argument,
not a different calling convention:

    sess = open_index(X, index="ivf", method="DADE", backend="host")
    res = sess.search(Q, k=10, nprobe=16)        # batched; res.ids (nq, k)
    sess.add(X_new)                              # dynamic inserts, no refit
    sess.save("idx.bin"); sess = SearchSession.load("idx.bin")
"""
from __future__ import annotations

import time

import numpy as np

from repro.api.backends import make_backend
from repro.api.types import SchedulePolicy, SearchResult
from repro.core.methods import ALL_METHODS, make_method
from repro.search.hnsw import HNSWIndex
from repro.search.ivf import IVFIndex
from repro.utils.spans import span

INDEX_KINDS = ("flat", "ivf", "hnsw")
#: facade name of every paper method -> backends that can serve it natively.
#: (Methods not listed under "jax" still run there via the exact lower-bound
#: fallback of their ``device_state()`` export.)
METHODS = tuple(ALL_METHODS)


class SearchSession:
    """A fitted method + built index + backend, behind batched calls."""

    def __init__(self, method, index_kind: str, index, backend: str = "host",
                 policy: SchedulePolicy | None = None, *, mesh=None):
        if index_kind not in INDEX_KINDS:
            raise ValueError(f"index must be one of {INDEX_KINDS}, got {index_kind!r}")
        self.method = method
        self.index_kind = index_kind
        self.index = index
        self.policy = policy if policy is not None else SchedulePolicy()
        self.backend = make_backend(backend, method, index_kind, index,
                                    self.policy, mesh=mesh)
        self.last_write_mode: str | None = None   # set by add()
        self.wal = None   # DeltaWAL once save()/load() ties a path to us

    # -- introspection -------------------------------------------------------
    @property
    def n(self) -> int:
        """Number of indexed vectors."""
        return int(self.method.state["N"])

    @property
    def dim(self) -> int:
        """Vector dimensionality."""
        return int(self.method.state["D"])

    @property
    def backend_name(self) -> str:
        """Executing backend: ``"host"`` or ``"jax"``."""
        return self.backend.name

    # -- online --------------------------------------------------------------
    def search(self, Q, k: int = 10, *, nprobe: int = 16, ef: int = 64,
               deadline_s: float | None = None) -> SearchResult:
        """Batched top-k for all rows of ``Q``; one online prep for the whole
        batch (the paper's O(D^2) per-query rotation, amortized).

        ``deadline_s`` arms anytime search (DESIGN.md §7): the scan stops
        after the last row-block (jax: block group) that finishes within
        ``deadline_s`` seconds of wall time and returns the running top-k as
        a partial result.  Partial queries report ``coverage < 1.0`` and a
        set ``uncertified_mask`` bit in ``result.stats.extra``; with a
        generous deadline the result is bit-identical to the non-deadline
        path.  Flat/IVF only (HNSW walks and mesh scans reject it)."""
        with span("search.prep"):
            Q = np.atleast_2d(np.asarray(Q))
            if Q.dtype.kind not in "fiu":
                raise ValueError(
                    f"search(): expected a numeric query array, got dtype {Q.dtype}")
            Q = np.ascontiguousarray(Q, np.float32)
            if not np.isfinite(Q).all():
                bad = int((~np.isfinite(Q).all(axis=1)).sum())
                raise ValueError(
                    f"search(): {bad} of {Q.shape[0]} queries contain NaN/Inf "
                    "values; distances to non-finite queries are meaningless "
                    "and would poison the running top-k threshold")
            if deadline_s is not None and deadline_s <= 0.0:
                raise ValueError(
                    f"search(): deadline_s must be > 0 (got {deadline_s}); the "
                    "engines always finish at least one block group, so a "
                    "non-positive budget cannot mean 'return nothing'")
        t0 = time.perf_counter()
        dists, ids, stats = self.backend.search(Q, k, nprobe=nprobe, ef=ef,
                                                deadline_s=deadline_s)
        return SearchResult(dists, ids, stats, time.perf_counter() - t0,
                            self.backend.name)

    def add(self, Xnew) -> "SearchSession":
        """Dynamic inserts (paper §V-E): extend the fitted method state
        without refitting transforms, then link/assign into the index.

        On the jax backend inserts below ``policy.delta_merge_threshold``
        rows land in a delta segment scanned alongside the cached main block
        layout (no re-materialization; DESIGN.md §6); the last write mode
        taken is readable as ``session.last_write_mode``.

        When the session is tied to a snapshot path (after ``save()`` or
        ``load()``), the rows are first written to the crash-safe delta WAL
        (fsync'd, before any state changes; DESIGN.md §7) — a crash at any
        point after ``add()`` returns loses nothing, and a crash mid-write
        tears only a frame that was never acknowledged."""
        Xnew = np.atleast_2d(np.asarray(Xnew))
        if Xnew.dtype.kind not in "fiu":
            raise ValueError(
                f"add(): expected a numeric array, got dtype {Xnew.dtype}")
        if Xnew.ndim != 2:
            raise ValueError(
                f"add(): expected (n, D) vectors, got shape {Xnew.shape}")
        if Xnew.shape[1] != self.dim:
            raise ValueError(
                f"add(): vectors have dimension {Xnew.shape[1]}, but this "
                f"index was built with D={self.dim}")
        Xnew = np.ascontiguousarray(Xnew, np.float32)
        if not np.isfinite(Xnew).all():
            bad = int((~np.isfinite(Xnew).all(axis=1)).sum())
            raise ValueError(
                f"add(): {bad} of {Xnew.shape[0]} rows contain NaN/Inf "
                "values; a non-finite corpus row poisons every distance "
                "computed against it (and the streaming engine's running "
                "tau), so it is rejected before any state or WAL write")
        if self.wal is not None:
            from repro.testing import faults
            self.wal.append(Xnew, self.n, plan=faults.active(self.policy))
        return self._apply_add(Xnew)

    def _apply_add(self, Xnew: np.ndarray) -> "SearchSession":
        """The state mutation of :meth:`add`, sans validation and WAL
        logging — the WAL's ``replay()`` calls this directly so replayed
        frames are not re-logged."""
        parts = None
        if self.index_kind == "hnsw":
            # insert_batch appends to the method itself, then links
            self.index.insert_batch(self.method, Xnew,
                                    schedule=self.policy.stage_dims(self.dim))
        else:
            start = self.n
            self.method.append(Xnew)
            if self.index_kind == "ivf":
                parts = self.index.insert(
                    np.arange(start, start + Xnew.shape[0]), Xnew)
        self.last_write_mode = self.backend.notify_append(
            Xnew.shape[0], parts=parts)
        return self

    def guardrails(self) -> dict | None:
        """Guardrail snapshot (DESIGN.md §9) when the session was opened
        with ``SchedulePolicy(guardrails=...)``: breaker state, drift/audit
        EWMAs, audit counters, and the transition log.  ``None`` when no
        guardrail is armed (including FDScanning sessions, which are
        already the certified fallback)."""
        g = getattr(self.backend, "guardrail", None)
        return None if g is None else g.report()

    def serve(self, **kwargs) -> "SearchService":
        """Wrap this session in a continuous-batching serving front
        (``repro.serving.SearchService``); kwargs are its knobs
        (slots/k/nprobe/...)."""
        from repro.serving.search_service import SearchService
        return SearchService(self, **kwargs)

    # -- persistence ---------------------------------------------------------
    def save(self, path) -> None:
        """Persist the fitted state + index to ``path`` (api.persistence)
        and arm the crash-safe delta WAL at ``path + ".wal"`` — later
        ``add()`` calls are logged there and survive a crash (the log is
        cleared first: this snapshot supersedes it)."""
        from repro.api.persistence import save_session
        save_session(self, path)

    @classmethod
    def load(cls, path, *, backend: str | None = None, mesh=None) -> "SearchSession":
        """Rebuild a saved session and replay its delta WAL (inserts made
        after the snapshot); ``backend``/``mesh`` may be overridden.
        Raises ``api.IndexLoadError`` on an unreadable snapshot."""
        from repro.api.persistence import load_session
        return load_session(path, backend=backend, mesh=mesh)


def open_index(X=None, *, index: str = "flat", method: str = "DADE",
               backend: str | None = None,
               schedule: SchedulePolicy | None = None,
               method_params: dict | None = None,
               index_params: dict | None = None,
               train_queries=None, train_k: int = 10,
               seed: int = 0, mesh=None, serving: bool = False,
               serving_params: dict | None = None, path=None):
    """Fit ``method`` on ``X``, build ``index``, and return a ready session.

    ``method`` is one of the paper's 8 (``repro.api.METHODS``); training-based
    methods (DDCpca/DDCopq) are trained on ``train_queries`` (default: a
    sample of X rows) for ``k=train_k``.  ``schedule`` tunes staging on both
    backends (default ``backend="host"``) — including
    ``SchedulePolicy(dim_groups=...)``, which switches the jax streaming
    engine to the PDX vertical layout with per-group early exit and makes
    the host scan read lower-bound stages incrementally (DESIGN.md §8);
    ``mesh`` (jax backend only) shards
    the corpus for a distributed global top-k.  ``serving=True`` wraps the
    session in a continuous-batching ``repro.serving.SearchService``
    (``serving_params`` are its knobs) and returns that instead.

    ``path`` ties the session to a snapshot file (DESIGN.md §7).  With
    ``X=None`` the session is *loaded* from ``path`` — snapshot plus a
    replay of its delta WAL, so inserts acknowledged after the last
    ``save()`` survive a crash (``IndexLoadError`` on unreadable files).
    With both given, the fresh index is immediately saved to ``path``,
    arming the WAL for every later ``add()``.
    """
    if X is None:
        if path is None:
            raise ValueError("open_index(): pass vectors X to build an "
                             "index, or path= to load a saved one")
        sess = SearchSession.load(path, backend=backend, mesh=mesh)
        if serving:
            return sess.serve(**(serving_params or {}))
        return sess
    backend = backend if backend is not None else "host"
    X = np.ascontiguousarray(np.atleast_2d(X), np.float32)
    policy = schedule if schedule is not None else SchedulePolicy()
    if method not in ALL_METHODS:
        raise ValueError(f"method must be one of {METHODS}, got {method!r}")
    # fail before paying for an index the backend can't serve
    if backend == "jax" and index == "hnsw":
        raise ValueError(
            f"backend='jax' serves index='flat' or 'ivf' (got {index!r}); "
            "HNSW graph walks are host-side indexes")
    if backend == "jax" and index == "ivf" and mesh is not None:
        raise ValueError(
            "device IVF probing is single-device; mesh-shard a flat corpus "
            "instead")
    m = make_method(method, **{"seed": seed, **(method_params or {})})
    m.fit(X)
    if m.needs_training:
        if train_queries is None:
            rng = np.random.default_rng(seed)
            train_queries = X[rng.choice(X.shape[0], min(24, X.shape[0]),
                                         replace=False)]
        m.train(np.asarray(train_queries, np.float32), train_k,
                policy.stage_dims(X.shape[1]))

    params = dict(index_params or {})
    if index == "flat":
        idx = None
    elif index == "ivf":
        params.setdefault("n_list", 64)
        idx = IVFIndex(**params).build(X)
    elif index == "hnsw":
        idx = HNSWIndex(**params).build(X, method=m,
                                        schedule=policy.stage_dims(X.shape[1]))
    else:
        raise ValueError(f"index must be one of {INDEX_KINDS}, got {index!r}")
    sess = SearchSession(m, index, idx, backend, policy, mesh=mesh)
    if path is not None:
        sess.save(path)
    if serving:
        return sess.serve(**(serving_params or {}))
    return sess
