"""Device (TPU) DCO engine: batched two-stage pruned top-k in pure JAX.

NOTE: since PR 2 the default device path is the streaming block-fused scan
in ``core.stream_engine`` (running tau, O(chunk·row_block) estimate memory);
this module keeps the engine config, the device-state builders, the
distributed wrapper, and the legacy one-shot engine
(``SchedulePolicy(engine="two_stage")``), which materializes a full
(query_chunk, N) estimate matrix per chunk.

This is the hardware adaptation of the paper's per-vector early-exit loop
(DESIGN.md §3).  Per query block:

  stage 0  rotate queries (the paper's O(D^2) online pre-processing, batched
           into one (Q,D)@(D,D) matmul);
  stage 1  partial squared distances over the leading ``d1`` rotated dims —
           one MXU matmul over a contiguous HBM stream;
  anchor   exact distances for the k best rows BY ESTIMATE (a k-row tail
           completion).  max of those k exact distances is a CERTIFIED upper
           bound tau on the true k-th distance, so for lower-bound methods
           (PDScanning/PDScanning+) the batch pipeline stays EXACT;
  stage 2  tail completion (trailing D-d1 rotated dims) only for a
           capacity-bounded set of survivors, then final top-k.

The rotated dataset is stored once, dimension-blocked, so "scan fewer
dimensions" literally becomes "stream fewer HBM bytes".

Decision rules supported (same estimators as core.methods):
  fdscan | lb (PDScanning/+) | adsampling | dade | ddcres | ratio (DDCpca)
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

#: Precision of every dot whose result is a lower bound, a completed
#: distance or tau.  A TPU f32 dot at default precision is one bf16 pass,
#: which breaks the certificate; HIGHEST keeps f32 accuracy.
HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class DcoEngineConfig:
    kind: str = "lb"           # fdscan|lb|adsampling|dade|ddcres|ratio
    d1: int = 128              # stage-1 dims
    k: int = 20
    capacity: int = 2048       # stage-2 survivor capacity per query per shard
    eps0: float = 2.1          # adsampling
    z_alpha: float = 2.0       # dade
    m: float = 3.0             # ddcres
    theta: float = 1.0         # ratio (DDCpca learned threshold)
    tau_slack: float = 1.0     # extra slack on the certified tau
    query_chunk: int = 16      # queries processed per lax.map step
    # --- streaming engine (core.stream_engine) knobs; ignored by two_stage ---
    row_block: int = 4096      # candidate rows streamed per lax.scan step
    block_capacity: int = 128  # survivors tail-completed per block per query
    use_kernel: bool | None = None  # Pallas dco_scan/pq_lookup for stage 1
                                    # (None -> only on TPU; CPU uses the
                                    # numerically identical jnp block path)
    policy: object | None = None    # core.policy.PolicyConfig for the
                                    # adaptive fdscan fallback (DESIGN.md §5);
                                    # None = fixed rule (frozen dataclass so
                                    # the config stays jit-static/hashable)
    dim_groups: int = 1        # PDX vertical layout: contiguous dim groups
                               # per row block with per-group early exit
                               # (DESIGN.md §8); 1 = flat row-major layout
    group_capacity: int = 0    # jnp PDX path: candidates kept per query
                               # after the group-0 R-cut (0 = auto:
                               # max(4*block_capacity, 512), clamped to the
                               # row block)


def build_device_state(method_or_arrays, d1: int) -> dict:
    """Build the dimension-blocked device arrays from a fitted host method's
    uniform ``device_state()`` export (or a raw dict with 'Xrot').  Requires a
    full-rank rotation so that lead+tail == exact (transforms.fit_pca
    guarantees rank==D for D<=1024; ADSampling rotations are full rank up to
    max_rank)."""
    if isinstance(method_or_arrays, dict):
        extras = method_or_arrays
    else:
        extras = method_or_arrays.device_state()
    xr = np.asarray(extras["Xrot"], np.float32)
    n, D = xr.shape
    d1 = min(d1, D)
    state = {
        "x_lead": jnp.asarray(xr[:, :d1]),
        "x_tail": jnp.asarray(xr[:, d1:]),
        "lead_sq": jnp.asarray((xr[:, :d1] ** 2).sum(1)),
        "tail_sq": jnp.asarray((xr[:, d1:] ** 2).sum(1)),
    }
    state.update(rule_scalars(extras, d1))
    return state


def rule_scalars(extras: dict, d1: int) -> dict:
    """Per-rule replicated scalars the engine's _estimate needs beyond the
    dimension-blocked arrays (DADE eigen-mass/slack at d1).  Shared by
    build_device_state and the mesh path, where the sharded per-device state
    is assembled inside shard_map and these ride along as constants."""
    out = {}
    if "mass" in extras:        # dade eigen-mass at d1
        out["mass_d1"] = jnp.float32(max(float(extras["mass"][d1 - 1]), 1e-9))
        out["eps_d1"] = jnp.float32(float(extras["eps_d"][d1 - 1]))
    return out


def _estimate(cfg: DcoEngineConfig, partial, D, state, q_extra):
    d1 = cfg.d1
    if cfg.kind in ("lb", "fdscan"):
        return partial
    if cfg.kind == "adsampling":
        return partial * (D / d1) / (1.0 + cfg.eps0 / np.sqrt(d1)) ** 2
    if cfg.kind == "dade":
        return partial / state["mass_d1"] / (1.0 + state["eps_d1"]) ** 2
    if cfg.kind == "ratio":
        return partial / cfg.theta
    if cfg.kind == "ddcres":
        # full-distance estimate: lead partial + exact tail norms, minus the
        # Gaussian slack on the unscanned cross term (core.methods Eq. 7);
        # per-query scalars arrive via q_extra (see api.backends.device_prep)
        slack = 2.0 * cfg.m * jnp.sqrt(jnp.maximum(q_extra["var_d1"], 0.0))
        return (partial + state["tail_sq"][None, :]
                + q_extra["qtail_sq"][:, None] - slack[:, None])
    raise ValueError(cfg.kind)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _two_stage_topk_padded(state: dict, q_lead: jax.Array, q_tail: jax.Array,
                           q_extra: dict, cfg: DcoEngineConfig):
    """Chunked two-stage top-k; requires nq to divide into query chunks."""
    x_lead, x_tail = state["x_lead"], state["x_tail"]
    n, d1 = x_lead.shape
    D = d1 + x_tail.shape[1]
    k, C = cfg.k, min(cfg.capacity, n)

    def one_chunk(qs):
        ql, qt, qe = qs                                    # (c, d1), (c, Dt)
        # ---- stage 1: one contiguous-stream matmul --------------------
        partial = (state["lead_sq"][None, :]
                   - 2.0 * jnp.matmul(ql, x_lead.T, precision=HIGHEST)
                   + (ql ** 2).sum(1)[:, None])            # (c, n)
        partial = jnp.maximum(partial, 0.0)
        est = _estimate(cfg, partial, D, state, qe)
        if cfg.kind == "fdscan":
            exact = partial + (
                state["tail_sq"][None, :]
                - 2.0 * jnp.matmul(qt, x_tail.T, precision=HIGHEST)
                + (qt ** 2).sum(1)[:, None])
            dists, ids = jax.lax.top_k(-exact, k)
            return -dists, ids, jnp.full((ql.shape[0],), n, jnp.int32)
        # ---- anchor: certified tau from k exact completions -----------
        _, anchor = jax.lax.top_k(-est, k)                 # (c, k) best by estimate
        a_tail = x_tail[anchor]                            # (c, k, Dt)
        a_exact = (partial[jnp.arange(ql.shape[0])[:, None], anchor]
                   + jnp.maximum(((a_tail - qt[:, None, :]) ** 2).sum(-1), 0.0))
        tau = a_exact.max(-1) * cfg.tau_slack              # (c,) upper bound on true kth
        # ---- screening + capacity selection ---------------------------
        keep = est <= tau[:, None]
        score = jnp.where(keep, est, jnp.inf)
        neg_s, cand = jax.lax.top_k(-score, C)             # (c, C) survivors
        alive = jnp.isfinite(-neg_s)
        n_alive = alive.sum(-1).astype(jnp.int32)
        # ---- stage 2: tail completion only for survivors --------------
        c_tail = x_tail[cand]                              # (c, C, Dt)
        c_part = partial[jnp.arange(ql.shape[0])[:, None], cand]
        exact = c_part + jnp.maximum(((c_tail - qt[:, None, :]) ** 2).sum(-1), 0.0)
        exact = jnp.where(alive, exact, jnp.inf)
        dists, pos = jax.lax.top_k(-exact, k)
        ids = cand[jnp.arange(ql.shape[0])[:, None], pos]
        return -dists, ids, n_alive

    nq = q_lead.shape[0]
    c = min(cfg.query_chunk, nq)
    ql = q_lead.reshape(nq // c, c, -1)
    qt = q_tail.reshape(nq // c, c, -1)
    qe = {key: v.reshape(nq // c, c) for key, v in q_extra.items()}
    d, i, s = jax.lax.map(one_chunk, (ql, qt, qe))
    return (d.reshape(nq, k), i.reshape(nq, k), s.reshape(nq))


def two_stage_topk(state: dict, q_lead: jax.Array, q_tail: jax.Array,
                   cfg: DcoEngineConfig, q_extra: dict | None = None):
    """Top-k over the local shard for a batch of (already rotated) queries.

    q_lead (Q, d1), q_tail (Q, D - d1).  Ragged batches (``nq`` not a
    multiple of ``cfg.query_chunk``) are zero-padded to a whole number of
    chunks and the padding rows sliced off the results.  ``q_extra`` carries
    optional per-query scalars (DDCres tail norms / variance suffix).
    Returns (dists_sq (Q,k), ids (Q,k), survivors (Q,) number of stage-2
    rows actually alive).
    """
    q_extra = dict(q_extra or {})
    nq = q_lead.shape[0]
    if nq == 0:
        raise ValueError("two_stage_topk needs at least one query")
    c = min(cfg.query_chunk, nq)
    pad = (-nq) % c
    if pad:
        q_lead = jnp.pad(q_lead, ((0, pad), (0, 0)))
        q_tail = jnp.pad(q_tail, ((0, pad), (0, 0)))
        q_extra = {key: jnp.pad(v, (0, pad)) for key, v in q_extra.items()}
    d, i, s = _two_stage_topk_padded(state, q_lead, q_tail, q_extra, cfg)
    return d[:nq], i[:nq], s[:nq]


def _aligned_row_block(per_shard: int, row_block: int) -> int:
    """The largest divisor of ``per_shard`` that is <= ``row_block`` — the
    biggest certificate-safe streaming block for a mesh shard of that size
    (worst case 1, which is always safe)."""
    rb = max(1, min(int(row_block), int(per_shard)))
    while per_shard % rb:
        rb -= 1
    return rb


def shard_stream_blocks(xr, d1: int, row_block: int, mesh,
                        shard_axes=("data", "model")):
    """The streaming layout of a corpus whose rows are divided over
    ``shard_axes`` of ``mesh``, for :func:`make_distributed_topk` with
    ``layout=True``.

    ``xr`` is the rotated corpus (N, D) on the host.  Shard ``s`` of ``S``
    holds rows ``[s*N//S, (s+1)*N//S)`` and lays them out as
    ``core.stream_engine.build_stream_blocks`` lays out one device's corpus:
    ``nb`` blocks of ``B = min(row_block, largest shard)`` rows, the last
    one padded with zero rows whose id is -1, which the scan masks.  Ids are
    global.  Each shard is built on the host and copied to its devices on
    its own, so the layout is the one resident copy of the corpus and no
    device ever holds a second.

    Returns ``(blocks, shard_state)``: ``blocks`` the layout's keys (``xl``,
    ``xt``, ``lsq``, ``tsq``, ``ids``) as (S * nb, B, ...) arrays sharded on
    the block axis; ``shard_state`` ``{"tail_min": (S,)}``, each shard's
    least tail norm over its real rows (the DDCres screen's bound, which a
    pad row's zero norm would loosen)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    xr = np.asarray(xr, np.float32)
    n, D = xr.shape
    d1 = min(int(d1), D)
    S = int(np.prod([mesh.shape[a] for a in shard_axes]))
    lo = [s * n // S for s in range(S + 1)]
    most = max(lo[s + 1] - lo[s] for s in range(S))
    B = max(1, min(int(row_block), most))
    nb = max(1, -(-most // B))

    # squared norms of each shard's real lead and tail rows, as
    # build_device_state computes them
    norms = [((xr[lo[s]:lo[s + 1], :d1] ** 2).sum(1),
              (xr[lo[s]:lo[s + 1], d1:] ** 2).sum(1)) for s in range(S)]

    def real(key, s):
        """The real rows of shard ``s`` of the layout's array ``key``."""
        a, b = lo[s], lo[s + 1]
        if key == "xl":
            return xr[a:b, :d1]
        if key == "xt":
            return xr[a:b, d1:]
        if key == "ids":
            return np.arange(a, b, dtype=np.int32)
        return norms[s][key == "tsq"]

    def padded(key, s):
        """Shard ``s`` of ``key`` as (nb, B, ...) blocks; pad rows are zero
        with id -1, as build_stream_blocks pads."""
        a = real(key, s)
        out = np.full((nb * B,) + a.shape[1:], -1 if key == "ids" else 0,
                      a.dtype)
        out[:len(a)] = a
        return out.reshape(nb, B, *a.shape[1:])

    sharding = NamedSharding(mesh, P(tuple(shard_axes)))
    width = {"xl": (d1,), "xt": (D - d1,)}
    blocks = {
        key: jax.make_array_from_callback(
            (S * nb, B) + width.get(key, ()), sharding,
            lambda index, key=key: padded(key, (index[0].start or 0) // nb))
        for key in ("xl", "xt", "lsq", "tsq", "ids")}
    tail_min = np.array([t.min() if t.size else 0.0 for _, t in norms],
                        np.float32)
    return blocks, {"tail_min": jax.make_array_from_callback(
        (S,), sharding, lambda index: tail_min[index])}


def make_distributed_topk(mesh, cfg: DcoEngineConfig, shard_axes=("data", "model"),
                          extra_state: dict | None = None, engine: str = "stream",
                          n_rows: int | None = None, layout: bool = False):
    """shard_map engine: dataset rows sharded over ``shard_axes``; queries
    (and per-query ``q_extra`` scalars) replicated; local top-k per shard
    then all-gather + global merge.  The local engine is the streaming
    block-fused scan (core.stream_engine, the default) or the legacy
    ``two_stage`` materializing engine.  ``extra_state`` carries the
    replicated rule scalars from :func:`rule_scalars` (e.g. DADE
    mass_d1/eps_d1).

    With ``layout=True`` the returned function takes ``(blocks,
    shard_state, q_lead, q_tail, q_extra)``: the per-shard streaming layout
    of :func:`shard_stream_blocks`, built once and sharded on its block
    axis, and its ``shard_state``.  Each shard's last block is padded with
    zero rows of id -1, which the scan masks out of every screen,
    completion and counter exactly as it masks the padded last block of a
    one-device layout, and the DDCres screen's ``tail_min`` covers the real
    rows only, so the padding leaves each shard's certificate as strong as
    a one-device scan's.  The layout's ids are global.  Without ``layout``
    it takes the flat row-sharded arrays ``(x_lead, x_tail, lead_sq,
    tail_sq, q_lead, q_tail, q_extra)``, builds the layout inside every
    call and offsets each shard's ids by its first row.

    The streaming engine returns ``stream_topk``'s first seven outputs
    reduced over the shards: (dists (Q, k), ids (Q, k), survivors (Q,),
    passed (Q,), dropped_min_est (Q,), dims_read (Q,), shared_blocks (Q,)).
    The counters are summed over shards (psum): real completions, rows that
    passed the screen, dims read, and blocks completed chunk-shared out of
    every shard's blocks.  dropped_min_est is the global (pmin) exactness
    certificate.  The two-stage engine (flat arrays only) returns (dists,
    ids, survivors, dropped_min_est), the last +inf.  The gather, the
    global top-k and the reductions run under the ``dco.gather`` scope.

    ``n_rows`` (the total sharded row count, flat arrays only) arms
    build-time validation of the flat path's certificate sharp edge: there
    the layout is built inside the call from a shard whose row count is not
    a ``row_block`` multiple by padding the last block with zero rows, and
    the id offset turns those rows' -1 ids into real ones, so their
    estimates can sit under the running tau and weaken each shard's
    certificate (and, through the pmin merge, the global one).  Passing
    ``n_rows`` makes that misalignment a build-time error.  ``None``
    preserves the old caller-beware behavior; ``layout=True`` has no such
    edge."""
    from jax.sharding import PartitionSpec as P

    if engine not in ("stream", "two_stage"):
        raise ValueError(f"engine must be 'stream' or 'two_stage', got {engine!r}")
    if layout and engine != "stream":
        raise ValueError("a prebuilt layout is the streaming engine's; the "
                         "two_stage engine takes the flat arrays")
    if cfg.policy is not None and getattr(cfg.policy, "adaptive", False):
        raise ValueError(
            "the adaptive DCO policy is single-device for now — drop "
            "SchedulePolicy(adaptive=True) on the mesh path (DESIGN.md §5)")
    if n_rows is not None and not layout:
        n_shards = 1
        for a in shard_axes:
            n_shards *= mesh.shape[a]
        per_shard, rem = divmod(int(n_rows), n_shards)
        if rem:
            raise ValueError(
                f"make_distributed_topk: {n_rows} rows do not shard evenly "
                f"over {n_shards} devices ({shard_axes}); pad the corpus to "
                f"a multiple of {n_shards} rows before sharding")
        if engine == "stream" and per_shard % cfg.row_block:
            raise ValueError(
                f"make_distributed_topk: shard size {per_shard} is not a "
                f"multiple of row_block={cfg.row_block} — the per-shard "
                "streaming layout would pad the last block with phantom "
                "zero rows, weakening every shard's exactness certificate "
                "(DESIGN.md §4/§10).  Use a row_block that divides the "
                f"shard size (e.g. {_aligned_row_block(per_shard, cfg.row_block)}), "
                "pad the corpus, or pass layout=True with shard_stream_blocks")
    extra_state = dict(extra_state or {})

    def local_topk(state, blocks, row0, q_lead, q_tail, q_extra):
        """This shard's top-k, then the global merge and reductions."""
        if engine == "stream":
            from repro.core.stream_engine import stream_topk
            d, i, surv, passed, dmin, dims, shared = stream_topk(
                state, q_lead, q_tail, cfg, q_extra, blocks=blocks)
            summed = (surv, passed, dims, shared)
        else:
            d, i, surv = two_stage_topk(state, q_lead, q_tail, cfg, q_extra)
            dmin = jnp.full(d.shape[0], jnp.inf)
            summed = (surv,)
        if row0 is not None:
            i = i + row0
        with jax.named_scope("dco.gather"):
            dg = jax.lax.all_gather(d, shard_axes, tiled=False)   # (S, Q, k)
            ig = jax.lax.all_gather(i, shard_axes, tiled=False)
            dg = jnp.moveaxis(dg, 0, 1).reshape(d.shape[0], -1)   # (Q, S*k)
            ig = jnp.moveaxis(ig, 0, 1).reshape(d.shape[0], -1)
            best, pos = jax.lax.top_k(-dg, cfg.k)
            ids = jnp.take_along_axis(ig, pos, axis=1)
            summed = [jax.lax.psum(a, shard_axes) for a in summed]
            dmin = jax.lax.pmin(dmin, shard_axes)   # weakest shard certificate
        if engine == "stream":
            surv, passed, dims, shared = summed
            return -best, ids, surv, passed, dmin, dims, shared
        return -best, ids, summed[0], dmin

    spec_x = P(shard_axes)      # rows (or row blocks) sharded over the axes
    if layout:
        def local_fn(blocks, shard_state, q_lead, q_tail, q_extra):
            # the scan reads the DDCres bound from tail_min; tail_sq (here
            # the layout's own, pad rows included) is only looked up
            state = {**extra_state, **shard_state, "tail_sq": blocks["tsq"]}
            return local_topk(state, blocks, None, q_lead, q_tail, q_extra)
        in_specs = (spec_x, spec_x, P(), P(), P())
    else:
        def local_fn(x_lead, x_tail, lead_sq, tail_sq, q_lead, q_tail, q_extra):
            state = {"x_lead": x_lead, "x_tail": x_tail,
                     "lead_sq": lead_sq, "tail_sq": tail_sq, **extra_state}
            # globalize ids with the shard's row offset
            idx = jax.lax.axis_index(shard_axes[0])
            for a in shard_axes[1:]:
                idx = idx * jax.lax.psum(1, a) + jax.lax.axis_index(a)
            return local_topk(state, None, idx * x_lead.shape[0],
                              q_lead, q_tail, q_extra)
        in_specs = (spec_x, spec_x, spec_x, spec_x, P(), P(), P())
    return jax.shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                         out_specs=P(), check_vma=False)
