"""Streaming device DCO engine: block-fused corpus scan with a running top-k.

``core.jax_engine.two_stage_topk`` materializes a full (query_chunk, N)
estimate matrix in HBM and runs ``top_k`` over all N rows per chunk — O(N·Q)
memory and traffic that caps corpus size per device.  This engine instead
walks the rotated corpus in candidate row blocks under ``lax.scan``:

  per block   the fused ``dco_scan`` Pallas kernel computes stage-1 partial
              distances and screens against the *running* tau (its keep-count
              output is the per-block survivor tally, so no (N, Q) array
              ever leaves the loop);
  compaction  survivors are compacted on device — top-``block_capacity`` by
              estimate — and tail-completed (trailing D-d1 rotated dims);
  merge       completed rows fold into a carried per-query top-k whose k-th
              distance tightens tau for every later block — the monotone
              pruning a one-shot anchor tau cannot achieve.

Peak HBM for the estimate tile drops to O(chunk·row_block +
chunk·block_capacity), independent of N.  The running tau is certified (the
k-th best EXACT distance seen so far is always an upper bound on the true
k-th), so screening never prunes a true neighbor under a lower-bound rule;
exactness then holds whenever every screen survivor is tail-completed,
which the engine makes CHECKABLE: ``passed == survivors`` for a query
certifies that no block overflowed ``block_capacity`` (overflow = some
screen survivors were dropped by estimate-ranked compaction — the same
capacity-bounded caveat as the two-stage engine's ``capacity`` cut, at a
per-block granularity; see DESIGN.md §4 and the ``truncated_queries``
facade stat).

Decision rules: fdscan | lb | adsampling | dade | ddcres | ratio | opq.
``opq`` is DDCopq's PQ screening through the ``pq_lookup`` Pallas kernel —
the rule the two-stage engine can only serve via its exact lower-bound
fallback.

IVF probing (``probe=``): rows are laid out partition-major
(``state["row_part"]`` sorted, ``state["row_ids"]`` the permutation); blocks
whose partition span contains no probed partition get tau=-1, which the
dco_scan kernel's block-level early exit turns into skipped matmuls, and
individual rows of unprobed partitions are masked out of the keep set — a
device-side IVF probe over the same streamed layout as the flat scan.

PDX vertical layout (``dim_groups`` > 1, DESIGN.md §8): the lead dims of a
block are partitioned into contiguous dimension GROUPS — ``build_stream_blocks``
stores (n_blocks, G, block, dg) so each group is a unit-stride plane — and the
scan becomes progressive refinement: group 0 (the pure screening read) prices
every candidate row, survivors compact to a per-query top-``group_capacity``
candidate set whose +1 observer slot folds the best dropped group-0 estimate
into the exactness certificate, and later groups refine only the compacted
candidates, freezing each one whose running partial crosses the running tau.
A partial distance over any dim prefix is a valid lower bound under these
rules, so per-group freezing never needs a certificate entry; only the two
capacity cuts (R-cut and completion budget) do, and both are observed.  The
kernel path (``dco_scan_grouped``) keeps the same per-group freeze semantics
without the R-cut — dense MXU tiles with ``pl.when`` block skips are the
better trade on TPU.

On CPU (no TPU) the engine defaults to a jnp block path that is numerically
identical to the kernel semantics (same per-element arithmetic; the kernel's
mid-scan freezing only changes partials of rows that are masked anyway), so
tests and benchmarks exercise the same screening decisions the TPU runs.

With an adaptive ``core.policy.PolicyConfig`` on the config, the engine
additionally serves each block by whichever rule is winning (DESIGN.md §5):
a pre-scan seed certifies an initial tau and dispatches clearly-shifted
query chunks to a conditional-free full-scan body; all other chunks run the
screened scan with a ``PolicyState`` in the carry and a single per-block
escape that completes a block exactly when its survivors spill the
completion budget or the running cost model says screening is net-negative.
Screened blocks never drop rows under the policy, so adaptive scans are
certified by construction.
"""
from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.jax_engine import HIGHEST, DcoEngineConfig


def _round8(v: int) -> int:
    return max(8, -(-v // 8) * 8)


def _group_plan(d1: int, groups: int):
    """Resolve a requested ``dim_groups`` against the screening width: the
    lead dims split into contiguous groups of ``ceil(d1/G)`` dims (the last
    group may be ragged; the layout zero-pads it, which adds 0 to every
    squared-distance partial).  Returns (G, dg, widths) with ``widths`` the
    logical dim count per group — idempotent, so a delta segment rebuilt
    from the main layout's group count reproduces the same split."""
    G = max(1, min(int(groups), int(d1)))
    dg = -(-d1 // G)
    G = -(-d1 // dg)
    widths = tuple(min(dg, d1 - g * dg) for g in range(G))
    return G, dg, widths


def _effective_groups(cfg: DcoEngineConfig) -> int:
    """PDX group count the engine actually honors: ``fdscan`` has no screen
    to stage and ``opq`` screens on the PQ adist rather than lead partials,
    so both force the flat (G=1) layout."""
    if cfg.kind in ("fdscan", "opq"):
        return 1
    return max(1, int(cfg.dim_groups))


def _final_scale(cfg: DcoEngineConfig, state: dict, D: int):
    """Per-rule multiplier s such that screening is ``partial * s <= tau``.
    Used for every dim-block of the kernel: intermediate partials only grow,
    so testing them against the FINAL scale is conservative (never prunes a
    row the final test would keep) and needs no per-stage eigen-mass plumbing.
    """
    d1 = cfg.d1
    if cfg.kind in ("lb", "fdscan", "ddcres", "opq"):
        return jnp.float32(1.0)    # opq screens on PQ adist, not partials
    if cfg.kind == "adsampling":
        return jnp.float32((D / d1) / (1.0 + cfg.eps0 / np.sqrt(d1)) ** 2)
    if cfg.kind == "dade":
        return 1.0 / (state["mass_d1"] * (1.0 + state["eps_d1"]) ** 2)
    if cfg.kind == "ratio":
        return jnp.float32(1.0 / max(cfg.theta, 1e-9))
    raise ValueError(cfg.kind)


def _merge_topk(best_d, best_i, new_d, new_i, k: int):
    d = jnp.concatenate([best_d, new_d], axis=1)
    i = jnp.concatenate([best_i, new_i], axis=1)
    neg, pos = jax.lax.top_k(-d, k)
    return -neg, jnp.take_along_axis(i, pos, axis=1)


@functools.partial(jax.jit,
                   static_argnames=("row_block", "full_width", "dim_groups"))
def build_stream_blocks(state: dict, row_block: int,
                        full_width: bool = False,
                        dim_groups: int = 1) -> dict:
    """Pad the corpus to a whole number of row blocks and reshape every
    per-row array to (n_blocks, block, ...).  Pad rows carry id -1.  The
    layout depends only on the device state, ``row_block`` and
    ``dim_groups``, so callers that search repeatedly (api.backends
    .JaxBackend) build it ONCE per materialization instead of paying a
    full-corpus pad copy per query batch (N % row_block != 0 makes
    ``jnp.pad`` a real O(N*D) copy).

    ``dim_groups`` > 1 selects the PDX vertical layout (DESIGN.md §8): the
    lead dims split into contiguous groups per :func:`_group_plan` and
    ``xl`` becomes (n_blocks, G, block, dg) — dim-group-major, each group a
    unit-stride (block, dg) plane — with per-group squared norms under
    ``lsg`` (n_blocks, G, block) next to the flat ``lsq``.  A ragged last
    group zero-pads, contributing nothing to squared-distance partials.

    ``full_width=True`` keeps the block width at ``row_block`` even when the
    segment has fewer rows — required for a delta segment whose blocks are
    concatenated after a main layout of that width (append_stream_blocks)."""
    x_lead = state["x_lead"]
    n = x_lead.shape[0]
    B = row_block if full_width else min(row_block, n)
    nb = -(-n // B)
    pad = nb * B - n

    def rows(a, **kw):
        widths = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
        return jnp.pad(a, widths, **kw).reshape(nb, B, *a.shape[1:])

    ids = state.get("row_ids")
    if ids is None:
        ids = jnp.arange(n, dtype=jnp.int32)
    xs = {
        "xl": rows(x_lead),
        "xt": rows(state["x_tail"]),
        "lsq": rows(state["lead_sq"]),
        "tsq": rows(state["tail_sq"]),
        "ids": rows(ids.astype(jnp.int32), constant_values=-1),
    }
    if "row_part" in state:     # partition-major layout for IVF probing
        xs["part"] = rows(state["row_part"].astype(jnp.int32), mode="edge")
    if "codes" in state:        # PQ codes for the opq rule
        xs["codes"] = rows(state["codes"].astype(jnp.int32))
    if dim_groups > 1:
        d1 = x_lead.shape[1]
        G, dg, _ = _group_plan(d1, dim_groups)
        if G > 1:
            xp = jnp.pad(x_lead, ((0, pad), (0, G * dg - d1)))
            xg = jnp.moveaxis(xp.reshape(nb, B, G, dg), 2, 1)
            xs["xl"] = xg                                   # (nb, G, B, dg)
            xs["lsg"] = (xg ** 2).sum(-1)                   # (nb, G, B)
    return xs


def append_stream_blocks(main: dict, delta_state: dict) -> dict:
    """Concatenate a small delta segment's blocks after a main layout.

    The delta layout is built at the MAIN block width (``full_width=True``),
    so the combined pytree is one (nb_main + nb_delta, B, ...) stack the
    engine's ``lax.scan`` walks end to end — the running tau tightened over
    the main segment carries straight into the delta blocks (and vice versa
    on later batches), which is what makes the LSM-style write path free of
    any cross-segment merge step at query time.  ``delta_state`` must carry
    ``row_ids`` (global ids of the appended rows) and the same optional keys
    (``row_part``, ``codes``) as the main layout — and it inherits the
    main layout's PDX group count (``_group_plan`` is idempotent, so the
    rebuilt split matches group-for-group)."""
    B = main["xl"].shape[-2]
    G = main["xl"].shape[1] if main["xl"].ndim == 4 else 1
    delta = build_stream_blocks(delta_state, B, full_width=True, dim_groups=G)
    missing = set(main) ^ set(delta)
    if missing:
        raise ValueError(f"delta segment layout keys differ from main: {missing}")
    return {key: jnp.concatenate([main[key], delta[key]]) for key in main}


def _adaptive(cfg: DcoEngineConfig) -> bool:
    """True when ``cfg`` carries an active adaptive policy (core.policy);
    the pure fdscan rule has nothing to fall back to."""
    return (cfg.policy is not None and cfg.policy.adaptive
            and cfg.kind != "fdscan")


def _scan_blocks(cfg: DcoEngineConfig, state, xs, ql, qt, qe, pr, B, D,
                 q_ok=None, init_tau=None, init_ewma=None, forced=False,
                 init_carry=None, return_carry=False):
    """Inner lax.scan over corpus row blocks for one query chunk.

    When ``cfg.policy`` is adaptive, the carry also holds a ``PolicyState``
    (per-query EWMA of the block survivor fraction plus the chunk's current
    mode) and each block is served through either the screened compaction
    path or a full fdscan completion — the certified fallback of DESIGN.md
    §5.  ``q_ok`` masks padding queries out of the chunk-level decision;
    ``init_tau``/``init_ewma`` carry the pre-scan seed (certified tau upper
    bound + sample pass fraction); ``forced=True`` (python-static) runs the
    dedicated conditional-free full-scan body for chunks the seed already
    placed in fallback.

    ``init_carry``/``return_carry`` (fixed, non-adaptive path only) make the
    scan RESUMABLE: the anytime driver (DESIGN.md §7) walks the corpus in
    block groups, threading the full ``(best_d, best_i, tau, surv, passed,
    dims)`` carry between jit calls so a deadline can interrupt the scan at any
    group boundary with the running top-k intact.  Resuming over block
    groups replays the exact per-block step sequence of the one-shot scan,
    so an uninterrupted grouped scan is bit-identical to it.
    """
    from repro.core.policy import pass_threshold
    from repro.kernels import ref
    from repro.kernels.ops import (_on_tpu, dco_scan_grouped_op, dco_scan_op,
                                   pq_lookup_op)

    c = ql.shape[0]
    k = cfg.k
    C = min(cfg.block_capacity, B)
    d1, Dt = ql.shape[1], qt.shape[1]
    # Mosaic requires (8, 128)-multiple tiles on real TPUs; interpret mode
    # (CPU) keeps tight tiles so tests don't pay for lane padding.  The
    # pq_lookup query tile spans the whole (8-padded) chunk: its (N, Q)
    # output block is then the full lane dim, which Mosaic accepts
    if cfg.use_kernel and _on_tpu():
        kb = dict(block_n=256, block_q=128, block_d=128)
        kb_pq = dict(block_n=128, block_q=_round8(c))
    else:
        kb = dict(block_n=min(256, _round8(B)), block_q=_round8(c),
                  block_d=min(128, _round8(d1)))
        kb_pq = dict(block_n=min(128, _round8(B)), block_q=_round8(c))
    scale = _final_scale(cfg, state, D)
    scales_arr = jnp.full((-(-d1 // kb["block_d"]),), scale, jnp.float32)
    qt_sq = (qt ** 2).sum(1)
    if cfg.kind == "ddcres":
        slack = 2.0 * cfg.m * jnp.sqrt(jnp.maximum(qe["var_d1"], 0.0))
        # a delta segment (api.backends) may carry rows with a smaller tail
        # norm than any main row; the backend threads the combined min as a
        # scalar so the Eq. 7 partial screen stays as loose as fitted
        tail_min = state.get("tail_min", state["tail_sq"]).min()

    Cp = min(C + 1, B)      # +1 slot observes the best DROPPED estimate

    # ---- PDX vertical layout (DESIGN.md §8) -------------------------------
    grouped = xs["xl"].ndim == 4
    Gr = xs["xl"].shape[1] if grouped else 1
    if grouped:
        dgp = xs["xl"].shape[-1]
        gw = tuple(min(dgp, d1 - g * dgp) for g in range(Gr))  # logical dims
        qlg = jnp.moveaxis(
            jnp.pad(ql, ((0, 0), (0, Gr * dgp - d1))).reshape(c, Gr, dgp),
            1, 0)                                              # (Gr, c, dgp)
        qgsq = (qlg ** 2).sum(-1)                              # (Gr, c)
        # jnp path: survivors of the group-0 screen compact to the per-query
        # top-R by estimate before the remaining groups are gathered — the
        # flop saving that makes progressive refinement pay off without the
        # kernel's tile-level skip.  R >= C so the completion budget never
        # tightens; the R-cut has its own observer slot (certificate).
        R = cfg.group_capacity if cfg.group_capacity > 0 else max(4 * C, 512)
        R = max(min(R, B), C)
        Rp = min(R + 1, B)
        if cfg.use_kernel:
            scales_g = jnp.full((Gr,), scale, jnp.float32)
            widths_g = jnp.asarray(gw, jnp.float32)
            kb_g = dict(block_n=kb["block_n"], block_q=kb["block_q"])

    pol = cfg.policy if _adaptive(cfg) else None
    if pol is not None:
        # cost-model threshold on the survivor fraction (static at trace
        # time): opq screens n_sub LUT dims and completes all D original
        # dims; partial rules screen d1 and complete the D - d1 tail
        if cfg.kind == "opq":
            d_screen, d_complete = float(qe["lut"].shape[1]), float(D)
        else:
            d_screen, d_complete = float(d1), float(D - d1)
        thr = pass_threshold(D, d_screen, d_complete,
                             pol.fallback_margin, pol.overhead_dims)

    def _complete_screened(best_d, best_i, tau, keep, est, partial, blk):
        # ---- on-device compaction: top-C survivors by estimate ------------
        score = jnp.where(keep, est, jnp.inf)
        neg_s, cand = jax.lax.top_k(-score, Cp)               # (c, C [+1])
        # Column C (when present) is the best estimate among rows the budget
        # DROPPED: the exactness certificate — no true neighbor was lost iff
        # the final k-th distance stays below every dropped lower bound.  It
        # is read via a masked reduce and the extra column is disabled by
        # masking, NOT by slicing: XLA CPU only rewrites the top_k sort into
        # the O(n log k) TopK custom call when it feeds a single slice, and
        # a second column slice forced a full row sort (15x slower)
        col = jax.lax.broadcasted_iota(jnp.int32, (1, Cp), 1)
        dropped = -jnp.max(jnp.where(col == C, neg_s, -jnp.inf), -1)
        alive = (neg_s > -jnp.inf) & (col < C)
        rows = jnp.arange(c)[:, None]
        c_tail = blk["xt"][cand]                              # (c, Cp, Dt)
        tail = jnp.maximum(((c_tail - qt[:, None, :]) ** 2).sum(-1), 0.0)
        if cfg.kind == "opq":
            c_lead = blk["xl"][cand]
            exact = jnp.maximum(((c_lead - ql[:, None, :]) ** 2).sum(-1), 0.0) + tail
        else:
            exact = partial[rows, cand] + tail
        exact = jnp.where(alive, exact, jnp.inf)
        new_d, new_i = _merge_topk(best_d, best_i, exact, blk["ids"][cand], k)
        # min() keeps a tighter seeded tau alive until the running top-k
        # beats it; without a seed the k-th only decreases, so it's a no-op
        new_tau = jnp.minimum(tau, new_d[:, -1] * cfg.tau_slack)
        return (new_d, new_i, new_tau,
                alive.sum(-1).astype(jnp.int32), dropped)

    def _pdx_screen(blk, tau, tau_k, valid, rowhit):
        """Grouped progressive screen (PDX vertical layout, DESIGN.md §8).

        Group 0 — the contiguous screening read — prices every candidate
        row; survivors compact to the per-query top-``R`` by estimate with
        a +1 observer slot capturing the best estimate the R-cut DROPPED
        (``dropped0``, folded into the exactness certificate exactly like
        the completion budget's observer column); the remaining groups
        refine only the compacted candidates, freezing each one whose
        running partial crosses the running tau.  Frozen rows need no
        certificate entry: a partial over any dim prefix is a valid lower
        bound under these rules, so a row frozen above today's tau can
        never re-enter a top-k whose tau only tightens."""
        xg, lsg = blk["xl"], blk["lsg"]               # (G, B, dg), (G, B)
        ok = valid[None, :] if rowhit is None else (valid[None, :] & rowhit)
        enter = ok & (tau_k >= 0.0)[:, None]                  # (c, B)
        contrib0 = jnp.maximum(
            lsg[0][None, :]
            - 2.0 * jnp.matmul(qlg[0], xg[0].T, precision=HIGHEST)
            + qgsq[0][:, None], 0.0)                          # (c, B)
        dims_b = enter.sum(-1).astype(jnp.float32) * jnp.float32(gw[0])
        if cfg.kind == "ddcres":
            estf = (contrib0 + blk["tsq"][None, :]
                    + qe["qtail_sq"][:, None] - slack[:, None])
            alive = (enter & (contrib0 <= tau_k[:, None])
                     & (estf <= tau[:, None]))
            rank = estf
        else:
            rank = contrib0 * scale
            alive = enter & (rank <= tau_k[:, None])
        # R-cut: same masked-observer top_k idiom as _complete_screened
        score = jnp.where(alive, rank, jnp.inf)
        neg_s, cand = jax.lax.top_k(-score, Rp)               # (c, R [+1])
        col = jax.lax.broadcasted_iota(jnp.int32, (1, Rp), 1)
        dropped0 = -jnp.max(jnp.where(col == R, neg_s, -jnp.inf), -1)
        aliveR = (neg_s > -jnp.inf) & (col < R)               # (c, Rp)
        acc = jnp.take_along_axis(contrib0, cand, axis=1)     # (c, Rp)
        for g in range(1, Gr):
            if g > 1:   # re-test the partial accumulated through group g-1
                gate = (acc <= tau_k[:, None] if cfg.kind == "ddcres"
                        else acc * scale <= tau_k[:, None])
                aliveR = aliveR & gate
            dims_b = dims_b + (aliveR.sum(-1).astype(jnp.float32)
                               * jnp.float32(gw[g]))
            xc = xg[g][cand]                                  # (c, Rp, dg)
            contrib = jnp.maximum(
                lsg[g][cand]
                - 2.0 * jnp.einsum("cd,crd->cr", qlg[g], xc,
                                   precision=HIGHEST)
                + qgsq[g][:, None], 0.0)
            acc = jnp.where(aliveR, acc + contrib, acc)
        if cfg.kind == "ddcres":
            est = (acc + blk["tsq"][cand] + qe["qtail_sq"][:, None]
                   - slack[:, None])
            keep = aliveR & (acc <= tau_k[:, None]) & (est <= tau[:, None])
        else:
            est = acc * scale
            keep = aliveR & (est <= tau_k[:, None])
        return cand, acc, keep, est, dropped0, dims_b

    def _complete_compacted(best_d, best_i, tau, keep, est, acc, cand,
                            dropped0, blk):
        """Exact tail completion over the PDX-compacted candidate axis: the
        same top-``C`` masked-observer compaction as _complete_screened,
        gathering block rows through ``cand``; the R-cut's observed drop
        folds into the returned certificate value."""
        CpR = min(C + 1, Rp)
        score = jnp.where(keep, est, jnp.inf)
        neg_s, sel = jax.lax.top_k(-score, CpR)
        col = jax.lax.broadcasted_iota(jnp.int32, (1, CpR), 1)
        droppedC = -jnp.max(jnp.where(col == C, neg_s, -jnp.inf), -1)
        alive = (neg_s > -jnp.inf) & (col < C)
        rsel = jnp.take_along_axis(cand, sel, axis=1)         # (c, CpR)
        c_tail = blk["xt"][rsel]                              # (c, CpR, Dt)
        tail = jnp.maximum(((c_tail - qt[:, None, :]) ** 2).sum(-1), 0.0)
        exact = jnp.take_along_axis(acc, sel, axis=1) + tail
        exact = jnp.where(alive, exact, jnp.inf)
        new_d, new_i = _merge_topk(best_d, best_i, exact, blk["ids"][rsel], k)
        new_tau = jnp.minimum(tau, new_d[:, -1] * cfg.tau_slack)
        return (new_d, new_i, new_tau, alive.sum(-1).astype(jnp.int32),
                jnp.minimum(dropped0, droppedC))

    def _complete_all(best_d, best_i, tau, partial, ok, blk):
        # certified fallback: every candidate row is completed exactly over
        # all D dims, so nothing is dropped (dropped = +inf) and the
        # per-query exactness certificate is preserved by construction
        if partial is None:       # opq / PDX escape: lead recomputed in full
            partial = _lead_partial(blk)
        exact = partial + jnp.maximum(
            blk["tsq"][None, :]
            - 2.0 * jnp.matmul(qt, blk["xt"].T, precision=HIGHEST)
            + qt_sq[:, None], 0.0)
        exact = jnp.where(ok, exact, jnp.inf)
        new_d, new_i = _merge_topk(
            best_d, best_i, exact,
            jnp.broadcast_to(blk["ids"][None, :], (c, B)), k)
        new_tau = jnp.minimum(tau, new_d[:, -1] * cfg.tau_slack)
        return (new_d, new_i, new_tau, ok.sum(-1).astype(jnp.int32),
                jnp.full((c,), jnp.inf, jnp.float32))

    def step(carry, blk):
        best_d, best_i, tau, surv, passed, dims = carry
        valid = blk["ids"] >= 0                               # (B,)
        rowhit = None
        tau_k = jnp.full((c,), jnp.inf) if cfg.kind == "fdscan" else tau
        if cfg.kind == "ddcres":
            # partial <= tau_k is implied by the Eq. 7 estimate test below
            tau_k = tau + slack - qe["qtail_sq"] - tail_min
        if pr is not None:
            # block-level probe gate: partition-major rows mean each block
            # spans [pmin, pmax]; unprobed blocks get tau=-1, which the
            # kernel's pl.when(any(alive)) turns into skipped matmuls
            pmin, pmax = blk["part"].min(), blk["part"].max()
            hit = ((pr >= pmin) & (pr <= pmax)).any(-1)       # (c,)
            tau_k = jnp.where(hit, tau_k, -1.0)
            rowhit = (blk["part"][None, :, None] == pr[:, None, :]).any(-1)
        okm = valid[None, :] if rowhit is None else (valid[None, :] & rowhit)
        n_okq = okm.sum(-1).astype(jnp.float32)               # (c,)

        if grouped and not cfg.use_kernel:
            # PDX progressive refinement on the jnp path (DESIGN.md §8)
            cand, acc, keepR, estR, dropped0, dims_scr = _pdx_screen(
                blk, tau, tau_k, valid, rowhit)
            passed_b = keepR.sum(-1).astype(jnp.int32)
            new_d, new_i, new_tau, completed, dropped = _complete_compacted(
                best_d, best_i, tau, keepR, estR, acc, cand, dropped0, blk)
            dims_b = dims_scr + completed.astype(jnp.float32) * (D - d1)
            return ((new_d, new_i, new_tau, surv + completed,
                     passed + passed_b, dims + dims_b), dropped)

        passed_b = None
        if cfg.kind == "opq":
            if cfg.use_kernel:
                adist = pq_lookup_op(blk["codes"], qe["lut"], **kb_pq)
            else:
                adist = ref.pq_lookup_ref(blk["codes"], qe["lut"])
            est = adist.T / cfg.theta                         # (c, B)
            keep = (est <= tau[:, None]) & valid[None, :]
            partial = None
            dims_scr = n_okq * float(qe["lut"].shape[1])
        elif cfg.use_kernel and grouped:
            nvalid = valid.sum().astype(jnp.int32)
            p, kp, cnt, ad = dco_scan_grouped_op(
                blk["xl"], qlg, tau_k, scales_g, widths_g, nvalid, **kb_g)
            partial, keep = p.T, kp.T.astype(bool)            # (c, B)
            est = partial * scale
            passed_b = cnt.sum(0)       # the kernel's per-block keep counts
            dims_scr = ad.sum(0)        # measured dims entered per query
        elif cfg.use_kernel:
            nvalid = valid.sum().astype(jnp.int32)
            p, kp, cnt, ad = dco_scan_op(blk["xl"], ql, tau_k, scales_arr,
                                         nvalid, **kb)
            partial, keep = p.T, kp.T.astype(bool)            # (c, B)
            est = partial * scale
            passed_b = cnt.sum(0)       # the kernel's per-block keep counts
            dims_scr = ad.sum(0)        # measured dims entered per query
        else:
            partial = jnp.maximum(
                blk["lsq"][None, :]
                - 2.0 * jnp.matmul(ql, blk["xl"].T, precision=HIGHEST)
                + (ql ** 2).sum(1)[:, None], 0.0)             # (c, B)
            est = partial * scale
            keep = (est <= tau_k[:, None]) & valid[None, :]
            # flat jnp screen reads all d1 lead dims of every candidate row
            # of a probed block (tau_k < 0 marks a block the probe skips)
            dims_scr = jnp.where(tau_k >= 0.0, n_okq, 0.0) * float(d1)
        if cfg.kind == "ddcres":
            # full-distance estimate (core.methods Eq. 7) refines the
            # conservative in-kernel partial screen and drives compaction
            est = (partial + blk["tsq"][None, :]
                   + qe["qtail_sq"][:, None] - slack[:, None])
            keep = keep & (est <= tau[:, None])
            passed_b = None
        if rowhit is not None:
            keep = keep & rowhit
            passed_b = None
        if passed_b is None:
            passed_b = keep.sum(-1).astype(jnp.int32)

        if cfg.kind == "fdscan":
            exact = partial + jnp.maximum(
                blk["tsq"][None, :]
                - 2.0 * jnp.matmul(qt, blk["xt"].T, precision=HIGHEST)
                + qt_sq[:, None], 0.0)
            ok = okm
            exact = jnp.where(ok, exact, jnp.inf)
            new_d, new_i = _merge_topk(
                best_d, best_i, exact,
                jnp.broadcast_to(blk["ids"][None, :], (c, B)), k)
            n_done = ok.sum(-1).astype(jnp.int32)
            new_tau = jnp.full((c,), jnp.inf)
            return ((new_d, new_i, new_tau, surv + n_done, passed + n_done,
                     dims + n_okq * float(D)),
                    jnp.full((c,), jnp.inf))

        new_d, new_i, new_tau, completed, dropped = _complete_screened(
            best_d, best_i, tau, keep, est, partial, blk)
        comp_w = float(D if cfg.kind == "opq" else D - d1)
        dims_b = dims_scr + completed.astype(jnp.float32) * comp_w
        return ((new_d, new_i, new_tau, surv + completed,
                 passed + passed_b, dims + dims_b), dropped)

    # ---- adaptive serving (DESIGN.md §5) ----------------------------------
    # One lax.cond per block whose branches are SELF-CONTAINED (each computes
    # its own stage-1 partial): a conditional boundary through shared big
    # intermediates forces XLA to materialize them and breaks the fused
    # screen->compact chain, which measured 25-45% on CPU.  The mode is
    # decided from history — the seeded pre-scan pass fraction plus every
    # earlier block's telemetry — and the screened branch carries a rare
    # recompute-from-scratch SPILL escape (survivors over block_capacity
    # complete the block exactly), so screened blocks never drop rows and
    # adaptive scans are certified by construction.
    q_okm = jnp.ones((c,), bool) if q_ok is None else q_ok

    def _lead_partial(blk):
        xl = blk["xl"]
        if xl.ndim == 3:            # PDX grouped layout: sum per-group reads
            acc = jnp.zeros((c, xl.shape[-2]), jnp.float32)
            for g in range(Gr):
                acc = acc + jnp.maximum(
                    blk["lsg"][g][None, :]
                    - 2.0 * jnp.matmul(qlg[g], xl[g].T, precision=HIGHEST)
                    + qgsq[g][:, None], 0.0)
            return acc
        return jnp.maximum(
            blk["lsq"][None, :]
            - 2.0 * jnp.matmul(ql, xl.T, precision=HIGHEST)
            + (ql ** 2).sum(1)[:, None], 0.0)                 # (c, B)

    def _screen_of(partial, blk, tau, ok):
        """(est, keep) for this block under the running tau; ``partial`` is
        the lead partial (None for opq, which screens on the PQ adist)."""
        if cfg.kind == "opq":
            if cfg.use_kernel:
                adist = pq_lookup_op(blk["codes"], qe["lut"], **kb_pq)
            else:
                adist = ref.pq_lookup_ref(blk["codes"], qe["lut"])
            est = adist.T / cfg.theta
        elif cfg.kind == "ddcres":
            est = (partial + blk["tsq"][None, :]
                   + qe["qtail_sq"][:, None] - slack[:, None])
        else:
            est = partial * scale
        return est, (est <= tau[:, None]) & ok

    def step_adaptive(carry, blk):
        # ONE conditional per block: the screened body runs fused exactly
        # like the fixed engine, then an ESCAPE serves the block fully when
        # (a) the screen spilled its completion budget — the capacity cut
        # would drop rows, so the exact completion keeps the scan CERTIFIED
        # BY CONSTRUCTION — or (b) the running cost model says screening is
        # net-negative (mode, with hysteresis).  The escape recomputes the
        # lead from scratch so the common no-escape path stays fusible.
        best_d, best_i, tau, surv, passed, dims, ps = carry
        valid = blk["ids"] >= 0
        rowhit = None
        if pr is not None:
            rowhit = (blk["part"][None, :, None] == pr[:, None, :]).any(-1)
        ok = (jnp.broadcast_to(valid[None, :], (c, B)) if rowhit is None
              else (valid[None, :] & rowhit))
        n_ok = ok.sum(-1).astype(jnp.int32)
        nokf = n_ok.astype(jnp.float32)

        if grouped:
            # PDX under the policy: the R-cut joins the spill gate — a cut
            # that dropped ANY alive row escapes to the exact completion, so
            # screened blocks still never drop rows and the adaptive scan
            # stays certified by construction, now per dim group.  The
            # escape recomputes the full lead (group-aware _lead_partial) so
            # the common screened path keeps only (c, R) operands across the
            # cond boundary.
            tau_ka = (tau + slack - qe["qtail_sq"] - tail_min
                      if cfg.kind == "ddcres" else tau)
            cand, acc, keepR, estR, dropped0, dims_scr = _pdx_screen(
                blk, tau, tau_ka, valid, rowhit)
            passed_b = keepR.sum(-1).astype(jnp.int32)
            spill = (q_okm & ((passed_b > C) | ~jnp.isinf(dropped0))).any()
            esc = spill | ps["mode"]
            new_d, new_i, new_tau, completed, dropped = jax.lax.cond(
                esc,
                lambda: _complete_all(best_d, best_i, tau, None, ok, blk),
                lambda: _complete_compacted(best_d, best_i, tau, keepR, estR,
                                            acc, cand, dropped0, blk))
            dims_b = jnp.where(
                esc, dims_scr + nokf * float(D),
                dims_scr + completed.astype(jnp.float32) * float(D - d1))
        else:
            partial = None if cfg.kind == "opq" else _lead_partial(blk)
            est, keep = _screen_of(partial, blk, tau, ok)
            passed_b = keep.sum(-1).astype(jnp.int32)
            spill = (q_okm & (passed_b > C)).any()
            esc = spill | ps["mode"]
            # both completions live INSIDE the cond so an escaped block
            # (steady fallback, or a spill) never pays the screened
            # compaction; the escape reuses the stage-1 partial, which
            # crosses the boundary anyway as an operand of the screened
            # branch
            new_d, new_i, new_tau, completed, dropped = jax.lax.cond(
                esc,
                lambda: _complete_all(best_d, best_i, tau, partial, ok, blk),
                lambda: _complete_screened(best_d, best_i, tau, keep, est,
                                           partial, blk))
            dims_b = jnp.where(
                esc, nokf * (d_screen + d_complete),
                nokf * d_screen
                + completed.astype(jnp.float32) * d_complete)

        # policy evidence.  A SPILL means screening lost this block
        # outright (it still paid a full completion): full-strength
        # evidence, so chronic spills flip the chunk into steady fallback.
        # Other blocks contribute the real screen fraction, which keeps
        # recovery possible.  Cold non-spill blocks carry no signal
        # (tau=inf makes the screen trivial).
        frac = passed_b.astype(jnp.float32) / jnp.maximum(n_ok, 1)
        warm = (n_ok > 0) & ~jnp.isinf(tau)
        spill_evt = spill & ~ps["mode"]
        obs = (warm | spill_evt) & (n_ok > 0)
        sig = jnp.where(spill_evt, 1.0, frac)
        a = jnp.float32(pol.ewma_alpha)
        new_ewma = jnp.where(obs & (ps["n"] > 0),
                             a * sig + (1.0 - a) * ps["ewma"], ps["ewma"])
        new_ewma = jnp.where(obs & (ps["n"] == 0), sig, new_ewma)
        new_n = ps["n"] + obs
        # next block's mode: a chunk falls back when ANY member query's
        # model says screening is net-negative (correctness-first; batch
        # OOD queries together so they don't drag ID chunks), and recovers
        # only once every member is back under the hysteresis band
        live = q_okm & (new_n > 0)
        want = (live & (new_ewma > thr)).any()
        stay = (live & (new_ewma > thr * pol.hysteresis)).any()
        next_mode = jnp.where(ps["mode"], stay, want)
        # an escaped block paid the screen bookkeeping on top of the full
        # completion; a screened block saves the unscanned tail
        saved_blk = jnp.where(
            esc, -(d_screen + pol.overhead_dims) * n_ok,
            (n_ok - completed) * d_complete - pol.overhead_dims * n_ok)
        new_ps = {
            "ewma": new_ewma, "n": new_n, "mode": next_mode,
            "fb": ps["fb"] + esc.astype(jnp.int32),
            "saved": ps["saved"] + 2.0 * saved_blk,
        }
        return ((new_d, new_i, new_tau, surv + completed, passed + passed_b,
                 dims + dims_b, new_ps), (dropped, esc.astype(jnp.float32)))

    init = (jnp.full((c, k), jnp.inf, jnp.float32),
            jnp.full((c, k), -1, jnp.int32),
            jnp.full((c,), jnp.inf, jnp.float32),
            jnp.zeros((c,), jnp.int32), jnp.zeros((c,), jnp.int32),
            jnp.zeros((c,), jnp.float32))
    if pol is None:
        if init_carry is not None:
            init = init_carry
        carry, dropped = jax.lax.scan(step, init, xs)
        if return_carry:
            return carry, dropped.min(0)
        d, i, _, surv, passed, dims = carry
        return d, i, surv, passed, dropped.min(0), dims

    nb = xs["xl"].shape[0]
    if init_tau is None:
        init_tau = jnp.full((c,), jnp.inf, jnp.float32)
    if init_ewma is None:
        init_ewma = jnp.zeros((c,), jnp.float32)
        init_n = jnp.zeros((c,), jnp.int32)
    elif cfg.kind == "opq":         # opq seed evidence needs adist: neutral
        init_ewma = jnp.zeros((c,), jnp.float32)
        init_n = jnp.zeros((c,), jnp.int32)
    else:
        init_n = jnp.ones((c,), jnp.int32)
    init = init[:2] + (init_tau,) + init[3:]

    if forced:
        # the whole chunk starts in fallback (the seed already said
        # screening is net-negative): serve it with a dedicated fused body —
        # the switching machinery never enters this graph, so a shifted
        # chunk costs ~a plain full scan plus the seed
        def step_full(carry, blk):
            best_d, best_i, tau, surv, passed, dims = carry
            valid = blk["ids"] >= 0
            if pr is None:
                ok = jnp.broadcast_to(valid[None, :], (c, B))
            else:
                rowhit = (blk["part"][None, :, None] == pr[:, None, :]).any(-1)
                ok = valid[None, :] & rowhit
            exact = _lead_partial(blk) + jnp.maximum(
                blk["tsq"][None, :]
                - 2.0 * jnp.matmul(qt, blk["xt"].T, precision=HIGHEST)
                + qt_sq[:, None], 0.0)
            exact = jnp.where(ok, exact, jnp.inf)
            nd, ni = _merge_topk(
                best_d, best_i, exact,
                jnp.broadcast_to(blk["ids"][None, :], (c, B)), k)
            ntau = jnp.minimum(tau, nd[:, -1] * cfg.tau_slack)
            n_ok = ok.sum(-1).astype(jnp.int32)
            return (nd, ni, ntau, surv + n_ok, passed + n_ok,
                    dims + n_ok.astype(jnp.float32) * float(D)), None

        (d, i, _, surv, passed, dims), _ = jax.lax.scan(step_full, init, xs)
        report = {"fb": jnp.full((c,), nb, jnp.int32),
                  "saved": jnp.zeros((c,), jnp.float32),
                  "timeline": jnp.ones((nb,), jnp.float32)}
        return (d, i, surv, passed, jnp.full((c,), jnp.inf, jnp.float32),
                dims, report)

    ini = init + ({"ewma": init_ewma, "n": init_n,
                   "mode": jnp.asarray(False),
                   "fb": jnp.asarray(0, jnp.int32),
                   "saved": jnp.zeros((c,), jnp.float32)},)
    (d, i, _, surv, passed, dims, ps), (dropped, modes) = jax.lax.scan(
        step_adaptive, ini, xs)
    report = {"fb": jnp.broadcast_to(ps["fb"], (c,)),
              "saved": ps["saved"], "timeline": modes}
    return d, i, surv, passed, dropped.min(0), dims, report


@functools.partial(jax.jit, static_argnames=("cfg",))
def _stream_topk_padded(state: dict, xs: dict, q_lead, q_tail, q_extra: dict,
                        probe, cfg: DcoEngineConfig):
    d1 = q_lead.shape[1]
    D = d1 + q_tail.shape[1]
    B = xs["xl"].shape[-2]
    nq = q_lead.shape[0]
    c = min(cfg.query_chunk, nq)
    ql = q_lead.reshape(nq // c, c, -1)
    qt = q_tail.reshape(nq // c, c, -1)
    qe = {key: v.reshape(nq // c, c, *v.shape[1:]) for key, v in q_extra.items()}
    pr = None if probe is None else probe.reshape(nq // c, c, -1)

    def one_chunk(args):
        cql, cqt, cqe, cpr = args
        return _scan_blocks(cfg, state, xs, cql, cqt, cqe, cpr, B, D)

    d, i, surv, passed, dmin, dims = jax.lax.map(one_chunk, (ql, qt, qe, pr))
    k = cfg.k
    return (d.reshape(nq, k), i.reshape(nq, k),
            surv.reshape(nq), passed.reshape(nq), dmin.reshape(nq),
            dims.reshape(nq))


@functools.partial(jax.jit, static_argnames=("cfg",))
def _anytime_group(state: dict, xs: dict, q_lead, q_tail, q_extra: dict,
                   probe, carry, cfg: DcoEngineConfig):
    """Resume the fixed streaming scan over ONE group of corpus blocks.

    ``carry`` is the whole padded batch's running state —
    ``(best_d (nq,k), best_i (nq,k), tau (nq,), surv (nq,), passed (nq,),
    dims (nq,), dropped_min (nq,))`` — threaded between jit calls by the
    anytime driver
    in :func:`stream_topk` (DESIGN.md §7).  Each call advances every query
    chunk by this group's blocks and returns the updated carry; the group
    boundary is the python-level point where the deadline is checked."""
    D = q_lead.shape[1] + q_tail.shape[1]
    B = xs["xl"].shape[-2]
    nq = q_lead.shape[0]
    c = min(cfg.query_chunk, nq)
    ql = q_lead.reshape(nq // c, c, -1)
    qt = q_tail.reshape(nq // c, c, -1)
    qe = {key: v.reshape(nq // c, c, *v.shape[1:]) for key, v in q_extra.items()}
    pr = None if probe is None else probe.reshape(nq // c, c, -1)
    cc = jax.tree_util.tree_map(
        lambda a: a.reshape(nq // c, c, *a.shape[1:]), carry)

    def one_chunk(args):
        cql, cqt, cqe, cpr, ccar = args
        new, dmin_g = _scan_blocks(cfg, state, xs, cql, cqt, cqe, cpr, B, D,
                                   init_carry=ccar[:6], return_carry=True)
        return new + (jnp.minimum(ccar[6], dmin_g),)

    out = jax.lax.map(one_chunk, (ql, qt, qe, pr, cc))
    return tuple(a.reshape(nq, *a.shape[2:]) for a in out)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _seed_eval(state: dict, xs: dict, q_lead, q_tail, q_extra: dict,
               cfg: DcoEngineConfig):
    """Pre-scan seed for the adaptive policy, over the whole padded batch.

    The k-th exact distance over a row sample upper-bounds the true k-th
    (CERTIFIED: screening against it can never prune a true neighbor under
    a lower-bound rule), and the sample's pass fraction against that tau
    estimates the corpus survivor fraction before any block is scanned.
    Expected pass rate vs the seeded tau is ~k/S per row, so S keeps early
    blocks under the spill gate (k/S * row_block << block_capacity).
    Returns (tau0 (nq,), ewma0 (nq,)).
    """
    B = xs["xl"].shape[-2]
    D = q_lead.shape[1] + q_tail.shape[1]
    S = min(1024, B)
    ql, qt = q_lead, q_tail
    sid = xs["ids"][0, :S]
    svalid = sid[None, :] >= 0
    xl0 = xs["xl"][0]
    if xl0.ndim == 3:               # PDX grouped layout (DESIGN.md §8)
        Gg, dgp = xl0.shape[0], xl0.shape[2]
        d1 = ql.shape[1]
        qg = jnp.moveaxis(
            jnp.pad(ql, ((0, 0), (0, Gg * dgp - d1))).reshape(
                ql.shape[0], Gg, dgp), 1, 0)
        lead_s = jnp.zeros((ql.shape[0], S), jnp.float32)
        for g in range(Gg):
            lead_s = lead_s + jnp.maximum(
                xs["lsg"][0][g, :S][None, :]
                - 2.0 * jnp.matmul(qg[g], xl0[g, :S].T, precision=HIGHEST)
                + (qg[g] ** 2).sum(1)[:, None], 0.0)
    else:
        lead_s = jnp.maximum(
            xs["lsq"][0, :S][None, :]
            - 2.0 * jnp.matmul(ql, xl0[:S].T, precision=HIGHEST)
            + (ql ** 2).sum(1)[:, None], 0.0)
    ex = lead_s + jnp.maximum(
        xs["tsq"][0, :S][None, :]
        - 2.0 * jnp.matmul(qt, xs["xt"][0, :S].T, precision=HIGHEST)
        + (qt ** 2).sum(1)[:, None], 0.0)
    ex = jnp.where(svalid, ex, jnp.inf)
    neg, _ = jax.lax.top_k(-ex, min(cfg.k, S))
    tau0 = -neg[:, -1] * cfg.tau_slack
    if cfg.kind == "opq":           # opq evidence needs adist: stay neutral
        return tau0, jnp.zeros(ql.shape[0], jnp.float32)
    if cfg.kind == "ddcres":
        slack = 2.0 * cfg.m * jnp.sqrt(jnp.maximum(q_extra["var_d1"], 0.0))
        est_s = (lead_s + xs["tsq"][0, :S][None, :]
                 + q_extra["qtail_sq"][:, None] - slack[:, None])
    else:
        est_s = lead_s * _final_scale(cfg, state, D)
    pass_s = ((est_s <= tau0[:, None]) & svalid).sum(-1)
    ewma0 = (pass_s / jnp.maximum(svalid.sum(-1), 1)).astype(jnp.float32)
    return tau0, ewma0


@functools.partial(jax.jit, static_argnames=("cfg", "forced"))
def _stream_chunk(state: dict, xs: dict, ql, qt, qe: dict, pr, qv, tau0, ew0,
                  cfg: DcoEngineConfig, forced: bool):
    """One query chunk through the adaptive engine (forced=True: the
    conditional-free full-scan body for chunks the seed put in fallback)."""
    D = ql.shape[1] + qt.shape[1]
    B = xs["xl"].shape[-2]
    return _scan_blocks(cfg, state, xs, ql, qt, qe, pr, B, D, q_ok=qv,
                        init_tau=tau0, init_ewma=ew0, forced=forced)


def _anytime_topk(state: dict, blocks: dict, q_lead, q_tail, q_extra: dict,
                  probe, cfg: DcoEngineConfig, nq: int, deadline_ts: float,
                  block_group: int):
    """Deadline-aware anytime driver (DESIGN.md §7): python loop over block
    groups, one host sync + wall check per group, early exit with the
    running top-k on expiry.  Returns the 6-tuple of :func:`stream_topk`
    plus ``coverage`` (fraction of corpus blocks scanned)."""
    from repro.testing import faults

    fp = faults.active()
    nqp, k = q_lead.shape[0], cfg.k
    carry = (jnp.full((nqp, k), jnp.inf, jnp.float32),
             jnp.full((nqp, k), -1, jnp.int32),
             jnp.full((nqp,), jnp.inf, jnp.float32),
             jnp.zeros((nqp,), jnp.int32),
             jnp.zeros((nqp,), jnp.int32),
             jnp.zeros((nqp,), jnp.float32),
             jnp.full((nqp,), jnp.inf, jnp.float32))
    nb = blocks["xl"].shape[0]
    G = max(1, int(block_group))
    done = 0
    while done < nb:
        g = min(G, nb - done)
        xs_g = {key: v[done:done + g] for key, v in blocks.items()}
        carry = _anytime_group(state, xs_g, q_lead, q_tail, q_extra, probe,
                               carry, cfg)
        done += g
        # the sync that makes the wall check honest: without it the python
        # loop races ahead of the async device queue and the deadline only
        # fires after every group has already been dispatched
        jax.block_until_ready(carry[0])
        faults.sleep_block(fp)
        if time.monotonic() > deadline_ts:
            break
    d, i, _, surv, passed, dims, dmin = carry
    return (d[:nq], i[:nq], surv[:nq], passed[:nq], dmin[:nq], dims[:nq],
            done / nb)


def stream_topk(state: dict, q_lead, q_tail, cfg: DcoEngineConfig,
                q_extra: dict | None = None, probe=None, blocks=None,
                deadline_ts: float | None = None, block_group: int = 8):
    """Streaming top-k over the local corpus for a batch of rotated queries.

    q_lead (Q, d1), q_tail (Q, D - d1).  ``state`` is a
    ``jax_engine.build_device_state`` export, optionally extended with
    ``row_ids`` (original ids when rows were permuted), ``row_part`` +
    ``probe`` (Q, nprobe) for IVF probing, and ``codes`` for the opq rule.
    ``blocks`` is an optional pre-built :func:`build_stream_blocks` layout
    (built here when absent — repeat callers should cache it; it must have
    been built with the group count :func:`_effective_groups` resolves for
    ``cfg``).  Ragged batches pad to a whole number of query chunks; N need
    not divide ``cfg.row_block``.  Returns (dists_sq (Q, k), ids (Q, k),
    survivors (Q,) rows tail-completed, passed (Q,) rows passing the screen,
    dropped_min_est (Q,) the smallest estimate among screen survivors any
    capacity cut dropped (+inf when nothing was dropped), dims_read (Q,)
    total dimensions the scan touched for the query — screening reads plus
    completed tails — the telemetry behind the facade's ``dims_read_mean``).
    ``dropped_min_est[q] > dists_sq[q, k-1]`` CERTIFIES exactness for
    lower-bound rules: every dropped row's lower bound exceeds the returned
    k-th distance, so no true neighbor was truncated.  A failed certificate
    means block_capacity should be raised (or row_block shrunk).

    ``cfg.dim_groups`` > 1 serves the scan from the PDX vertical layout
    (DESIGN.md §8): per-group progressive refinement with the R-cut's
    observer folded into ``dropped_min_est``, so the same certificate
    inequality covers group-level drops.  fdscan and opq force G=1.

    When ``cfg.policy`` is an adaptive ``core.policy.PolicyConfig`` the
    engine serves blocks adaptively (DESIGN.md §5) and appends a seventh
    return value, a report dict with per-query ``fallback_blocks`` /
    ``est_saved_flops`` and a per-block ``rule_timeline`` (fraction of query
    chunks served by fdscan).  Adaptive mode forces ``use_kernel=False`` for
    the dco_scan stage: the Pallas kernel freezes pruned rows mid-block, so
    its partials cannot be reused by the fallback branch's full completion
    (the pq_lookup path is unaffected).  A policy with
    ``force_fallback=True`` (the guardrail breaker's demotion, DESIGN.md
    §9) skips the seed entirely and serves EVERY chunk by the dedicated
    full-scan body — exact and certified by construction.

    ``deadline_ts`` (absolute ``time.monotonic()`` timestamp) arms ANYTIME
    mode (DESIGN.md §7): the corpus is walked in groups of ``block_group``
    row blocks, the running carry is synced and the wall clock checked at
    every group boundary, and on expiry the running top-k is returned as a
    partial result.  At least one group is always scanned.  The return
    gains a seventh element, ``coverage`` — the fraction of corpus blocks
    scanned (1.0 = the full scan, in which case results are bit-identical
    to the non-deadline path: the grouped scan replays the exact same
    per-block step sequence).  Queries with ``coverage < 1`` must be
    treated as UNCERTIFIED regardless of ``dropped_min_est`` (unscanned
    blocks may hold true neighbors); the facade's ``uncertified_mask``
    encodes this.  Anytime mode serves the fixed scan only — the backend
    strips an adaptive policy before a deadline call.
    """
    q_extra = dict(q_extra or {})
    adaptive = _adaptive(cfg)
    # adaptive mode forces the jnp dco_scan path (the kernel freezes pruned
    # rows mid-block, so its partials can't feed an escape's full
    # completion); opq screens via pq_lookup, whose adist is valid for all
    # rows, so it keeps its kernel
    force_jnp = adaptive and cfg.kind != "opq"
    if force_jnp and cfg.use_kernel:
        cfg = dataclasses.replace(cfg, use_kernel=False)
    if cfg.use_kernel is None:
        from repro.kernels.ops import _on_tpu
        cfg = dataclasses.replace(cfg, use_kernel=False if force_jnp
                                  else _on_tpu())
    ge = _effective_groups(cfg)
    if blocks is None:
        blocks = build_stream_blocks(state, cfg.row_block, dim_groups=ge)
    gb = blocks["xl"].shape[1] if blocks["xl"].ndim == 4 else 1
    gp = _group_plan(q_lead.shape[1], ge)[0] if ge > 1 else 1
    if gb != gp:
        raise ValueError(
            f"cached blocks layout has {gb} dim group(s) but cfg resolves "
            f"to {gp}: rebuild build_stream_blocks with dim_groups={ge}")
    nq = q_lead.shape[0]
    if nq == 0:
        raise ValueError("stream_topk needs at least one query")
    c = min(cfg.query_chunk, nq)
    pad = (-nq) % c
    if pad:
        q_lead = jnp.pad(q_lead, ((0, pad), (0, 0)))
        q_tail = jnp.pad(q_tail, ((0, pad), (0, 0)))
        q_extra = {key: jnp.pad(v, [(0, pad)] + [(0, 0)] * (v.ndim - 1))
                   for key, v in q_extra.items()}
        if probe is not None:
            probe = jnp.pad(probe, ((0, pad), (0, 0)))
    if deadline_ts is not None:
        if adaptive:
            raise ValueError(
                "anytime deadlines run the fixed streaming scan — strip the "
                "adaptive policy from cfg before a deadline call "
                "(DESIGN.md §7)")
        return _anytime_topk(state, blocks, q_lead, q_tail, q_extra, probe,
                             cfg, nq, deadline_ts, block_group)
    if not adaptive:
        d, i, s, p, dm, dr = _stream_topk_padded(state, blocks, q_lead,
                                                 q_tail, q_extra, probe, cfg)
        return d[:nq], i[:nq], s[:nq], p[:nq], dm[:nq], dr[:nq]

    # ---- adaptive orchestration (DESIGN.md §5) ----------------------------
    # Per-chunk python dispatch: the seed's pass fraction decides, per query
    # chunk and BEFORE any block is scanned, whether the chunk enters the
    # switching scan or the dedicated conditional-free full-scan body.  The
    # decision is one tiny host sync per batch; keeping it out of the jitted
    # graph avoids a whole-scan lax.cond, which measurably taxes the
    # executed branch on CPU.  (IVF probing gets no seed — sampled rows may
    # not be probe candidates — so probed chunks always run the switching
    # scan, whose spill gate keeps them certified.)
    from repro.core.policy import pass_threshold
    nqp = q_lead.shape[0]
    nchunks = nqp // c
    q_valid = jnp.arange(nqp) < nq
    if cfg.policy.force_fallback:
        # guardrail demotion (DESIGN.md §9): every chunk runs the dedicated
        # conditional-free full-scan body — certified by construction, no
        # seed pass needed (works for flat and IVF-probed scans alike)
        tau0 = ew0 = None
        chunk_full = np.ones(nchunks, bool)
    elif probe is None:
        tau0, ew0 = _seed_eval(state, blocks, q_lead, q_tail, q_extra, cfg)
        D = q_lead.shape[1] + q_tail.shape[1]
        if cfg.kind == "opq":
            d_screen, d_complete = float(q_extra["lut"].shape[1]), float(D)
        else:
            d_screen, d_complete = float(q_lead.shape[1]), float(D - q_lead.shape[1])
        thr = pass_threshold(D, d_screen, d_complete,
                             cfg.policy.fallback_margin,
                             cfg.policy.overhead_dims)
        chunk_full = np.asarray(
            (ew0 > thr) & q_valid).reshape(nchunks, c).any(1)
    else:
        tau0 = ew0 = None
        chunk_full = np.zeros(nchunks, bool)
    outs = []
    for ci in range(nchunks):
        sl = slice(ci * c, (ci + 1) * c)
        outs.append(_stream_chunk(
            state, blocks, q_lead[sl], q_tail[sl],
            {key: v[sl] for key, v in q_extra.items()},
            None if probe is None else probe[sl], q_valid[sl],
            None if tau0 is None else tau0[sl],
            None if ew0 is None else ew0[sl],
            cfg, bool(chunk_full[ci])))
    if nchunks == 1:
        d, i, s, p, dm, dr, rep = outs[0]
    else:
        d, i, s, p, dm, dr = (jnp.concatenate([o[j] for o in outs])
                              for j in range(6))
        rep = {key: jnp.concatenate([o[6][key] for o in outs])
               for key in ("fb", "saved")}
        rep["timeline"] = jnp.stack([o[6]["timeline"] for o in outs]).mean(0)
    report = {"fallback_blocks": rep["fb"][:nq],
              "est_saved_flops": rep["saved"][:nq],
              "rule_timeline": jnp.atleast_1d(rep["timeline"])}
    return d[:nq], i[:nq], s[:nq], p[:nq], dm[:nq], dr[:nq], report
