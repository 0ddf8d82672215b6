"""PDX vertical-layout coverage (DESIGN.md §8).

Parity: the dimension-grouped progressive scan must return bit-identical
top-k ids to the row-blocked stream engine (and the host scan) on every
draw — G=1 is the degenerate case and must be bitwise on distances too.
Certificate: every query either returns the exact brute-force top-k or has
its ``dropped_min_est`` certificate withdrawn; the adversarial decoy test
checks the R-cut's observer specifically (a drop that off-by-one-group
bookkeeping would silently lose).  Interactions: anytime deadlines, the LSM
delta segment, and the adaptive policy's verify-and-repair escape.

The hypothesis sweeps run only when hypothesis is installed (the plain
oracle tests below always run; tests/_hypothesis_compat.py skips just the
property tests otherwise).
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import SchedulePolicy, open_index
from repro.core.engine import (EXTRA_COVERAGE, EXTRA_DIMS_READ_MEAN,
                               EXTRA_UNCERTIFIED_MASK,
                               EXTRA_UNCERTIFIED_QUERIES)
from repro.core.jax_engine import DcoEngineConfig
from repro.core.policy import PolicyConfig
from repro.core.stream_engine import (_group_plan, build_stream_blocks,
                                      stream_topk)
from tests._hypothesis_compat import given, settings, st

K = 10


def _decayed(n, D, nq=5, seed=0, decay=12.0):
    """PCA-like spectrum: lead dims carry most energy, the regime where
    per-group early exit actually fires (isotropic data never crosses tau
    before ~d1 dims, so it exercises nothing)."""
    rng = np.random.default_rng(seed)
    s = np.exp(-np.arange(D) / decay).astype(np.float32)
    return ((rng.standard_normal((n, D)) * s).astype(np.float32),
            (rng.standard_normal((nq, D)) * s).astype(np.float32))


def _state(X, d1):
    return {"x_lead": jnp.asarray(X[:, :d1]), "x_tail": jnp.asarray(X[:, d1:]),
            "lead_sq": jnp.asarray((X[:, :d1] ** 2).sum(1)),
            "tail_sq": jnp.asarray((X[:, d1:] ** 2).sum(1))}


def _cfg(d1, k=K, **kw):
    base = dict(kind="lb", d1=d1, k=k, query_chunk=4, row_block=512,
                block_capacity=128, use_kernel=False)
    base.update(kw)
    return DcoEngineConfig(**base)


def _run(X, Q, cfg):
    st_ = _state(X, cfg.d1)
    out = stream_topk(st_, jnp.asarray(Q[:, :cfg.d1]),
                      jnp.asarray(Q[:, cfg.d1:]), cfg)
    return [np.asarray(v) for v in out]


def _brute(X, Q, k):
    d2 = ((X[None] - Q[:, None]) ** 2).sum(-1)
    i = np.argsort(d2, 1)[:, :k]
    return np.take_along_axis(d2, i, 1), i


# ------------------------------------------------------- group plan ---------
def test_group_plan_partitions_and_is_idempotent():
    """The split must cover d1 exactly with positive widths, and rebuilding
    a plan from its own resolved G must reproduce it (delta segments are
    rebuilt from the main layout's actual group count)."""
    for d1 in range(1, 70):
        for groups in range(1, 10):
            G, dg, widths = _group_plan(d1, groups)
            assert 1 <= G <= min(groups, d1)
            assert sum(widths) == d1 and all(w > 0 for w in widths)
            assert all(w <= dg for w in widths)
            assert _group_plan(d1, G) == (G, dg, widths)


# ----------------------------------------------------- parity sweep ---------
#: (n, D, d1, row_block, dim_groups, k) — ragged rows, ragged dim splits,
#: the G=1 degenerate, and k > block_capacity.
PARITY_CASES = [
    (1024, 96, 48, 256, 4, K),      # even splits
    (1000, 96, 48, 384, 5, K),      # N % row_block != 0, d1 % G != 0
    (777, 64, 40, 256, 3, K),       # everything ragged
    (600, 48, 48, 128, 4, K),       # no tail (d1 == D)
    (512, 96, 48, 512, 1, K),       # degenerate G=1: bitwise vs baseline
    (900, 96, 33, 200, 7, K),       # G close to group width 1
    (700, 96, 48, 128, 4, 200),     # k > block_capacity
]


@pytest.mark.parametrize("n,D,d1,rb,g,k", PARITY_CASES)
def test_pdx_matches_row_blocked_engine(n, D, d1, rb, g, k):
    bc = min(128, rb)
    base = _cfg(d1, k=k, row_block=rb, block_capacity=bc)
    pdx = dataclasses.replace(base, dim_groups=g)
    X, Q = _decayed(n, D, seed=n + g)
    d0, i0, s0, p0, dm0, r0, _ = _run(X, Q, base)
    d1_, i1, s1, p1, dm1, r1, _ = _run(X, Q, pdx)
    np.testing.assert_array_equal(i0, i1)       # ids bit-identical, always
    if g == 1:                                  # same code path: bitwise
        np.testing.assert_array_equal(d0, d1_)
        np.testing.assert_array_equal(np.asarray(dm0), np.asarray(dm1))
    else:                                       # grouped accumulation order
        np.testing.assert_allclose(d0, d1_, rtol=1e-5, atol=1e-5)
    # certificate soundness on BOTH engines: certified queries are exact
    bd, bi = _brute(X, Q, k)
    for qi in range(Q.shape[0]):
        if dm1[qi] > d1_[qi, -1]:
            np.testing.assert_array_equal(i1[qi], bi[qi])


def test_pdx_blocks_layout_guard():
    """Cached blocks built at one group count must be rejected by a cfg that
    resolves to another (the facade rebuilds; raw callers get a clear error
    instead of garbage gathers)."""
    X, Q = _decayed(512, 64, seed=3)
    st_ = _state(X, 32)
    blocks = build_stream_blocks(st_, 256, dim_groups=4)
    with pytest.raises(ValueError, match="dim group"):
        stream_topk(st_, jnp.asarray(Q[:, :32]), jnp.asarray(Q[:, 32:]),
                    _cfg(32, row_block=256), blocks=blocks)


@settings(max_examples=15, deadline=None)
@given(st.integers(64, 700), st.integers(2, 12), st.integers(1, 8),
       st.integers(1, 6), st.integers(0, 2 ** 31 - 1))
def test_pdx_parity_property(n, dim8, gfrac, rbfrac, seed):
    """Property sweep: for random corpus/query draws and random layout
    splits, PDX ids are bit-identical to the row-blocked engine and every
    certified query is exactly the brute-force top-k."""
    D = 8 * dim8
    d1 = max(1, D // 2)
    rb = max(64, n // rbfrac)
    g = min(gfrac, d1)
    k = min(K, n)
    X, Q = _decayed(n, D, nq=3, seed=seed % 10_000)
    base = _cfg(d1, k=k, row_block=rb, block_capacity=min(128, rb))
    d0, i0, *_ = _run(X, Q, base)
    d1_, i1, s1, p1, dm1, r1, _ = _run(X, Q, dataclasses.replace(
        base, dim_groups=g))
    np.testing.assert_array_equal(i0, i1)
    bd, bi = _brute(X, Q, k)
    for qi in range(Q.shape[0]):
        if dm1[qi] > d1_[qi, -1]:
            np.testing.assert_array_equal(i1[qi], bi[qi])


# ------------------------------------------------- adversarial decoys -------
def _decoy_corpus():
    """Block 0: 64 near rows (the eventual tau) plus far rows whose lead
    partial alone is enormous, so its completion cut only ever drops
    certified-prunable rows.  Block 1: 600 decoys whose group-0 partial is
    nearly zero but whose groups 1-2 carry a huge spike (they pass the
    screening read, then freeze mid-refinement), plus the true nearest
    neighbor whose group-0 partial is *worse* than every decoy — the auto
    R-cut (R=512 < 601) must drop it.  If the R-cut's observer were off by
    one group (or missing), the miss would go unflagged."""
    rng = np.random.default_rng(0)
    n0, nd, D, d1 = 2048, 600, 128, 48
    X = np.zeros((n0 + nd + 1, D), np.float32)
    X[:64] = rng.standard_normal((64, D)).astype(np.float32)   # exact ~ D
    X[64:n0, :d1] = 30.0                   # far: lead partial ~ 43k, huge
    X[n0:n0 + nd, :12] = rng.standard_normal((nd, 12)).astype(np.float32) / 8.0
    X[n0:n0 + nd, 12:36] = 20.0            # groups 1-2 spike (dg = 12)
    X[n0 + nd, 0] = 2.0                    # true NN: exact dist 4.0 to q=0
    q = np.zeros((1, D), np.float32)
    return X, q, n0 + nd, d1


def test_pdx_rcut_drop_is_flagged_not_silent():
    X, q, nn_id, d1 = _decoy_corpus()
    cfg = _cfg(d1, query_chunk=1, row_block=2048, block_capacity=64,
               dim_groups=4)                # auto R = max(4*64, 512) = 512
    d, i, s, p, dm, r, _ = _run(X, q, cfg)
    assert nn_id not in i[0]                # the R-cut dropped the true NN...
    assert float(dm[0]) <= float(d[0, -1])  # ...and the certificate says so


def test_pdx_group_capacity_restores_exactness():
    X, q, nn_id, d1 = _decoy_corpus()
    cfg = _cfg(d1, query_chunk=1, row_block=2048, block_capacity=64,
               dim_groups=4, group_capacity=2048)    # R = B: no cut
    d, i, s, p, dm, r, _ = _run(X, q, cfg)
    assert i[0, 0] == nn_id and float(d[0, 0]) == 4.0
    assert float(dm[0]) > float(d[0, -1])   # certified: nothing low dropped


def test_adaptive_repairs_pdx_rcut_drop():
    """The adaptive spill gate treats a finite R-cut drop like a capacity
    spill: the block escapes to the certified full completion, so the same
    corpus that the fixed PDX engine flags as a miss comes back exact."""
    X, q, nn_id, d1 = _decoy_corpus()
    cfg = _cfg(d1, query_chunk=1, row_block=2048, block_capacity=64,
               dim_groups=4, policy=PolicyConfig())
    d, i, s, p, dm, r, _, rep = _run(X, q, cfg)
    assert i[0, 0] == nn_id and float(d[0, 0]) == 4.0
    assert float(dm[0]) > float(d[0, -1])


# --------------------------------------------------- facade interactions ----
def _pol(**kw):
    base = dict(d1=48, query_chunk=4, row_block=256, block_capacity=256,
                dim_groups=4, use_kernel=False, anytime_block_group=2)
    base.update(kw)
    return SchedulePolicy(**base)


def test_pdx_host_and_jax_agree():
    X, Q = _decayed(1500, 96, seed=11)
    bd, bi = _brute(X, Q, K)
    rj = open_index(X, method="PDScanning", backend="jax",
                    schedule=_pol()).search(Q, K)
    rh = open_index(X, method="PDScanning", backend="host",
                    schedule=_pol(delta0=16, delta_d=16)).search(Q, K)
    np.testing.assert_array_equal(rj.ids, bi)
    np.testing.assert_array_equal(rh.ids, bi)
    assert rj.stats.extra[EXTRA_UNCERTIFIED_QUERIES] == 0.0
    # both paths measure dims actually read; early exit must beat a full
    # stage-1 read (d1 + completed tails) on this spectrum
    assert 0.0 < rj.stats.extra[EXTRA_DIMS_READ_MEAN] < 48.0
    assert 0.0 < rh.stats.extra[EXTRA_DIMS_READ_MEAN] < 96.0


def test_pdx_dims_read_smaller_than_flat():
    X, Q = _decayed(2000, 96, seed=13)
    r1 = open_index(X, method="PDScanning", backend="jax",
                    schedule=_pol(dim_groups=1)).search(Q, K)
    r4 = open_index(X, method="PDScanning", backend="jax",
                    schedule=_pol()).search(Q, K)
    np.testing.assert_array_equal(r1.ids, r4.ids)
    assert (r4.stats.extra[EXTRA_DIMS_READ_MEAN]
            < r1.stats.extra[EXTRA_DIMS_READ_MEAN])


def test_pdx_anytime_generous_deadline_bit_identical():
    X, Q = _decayed(1200, 96, seed=17)
    sess = open_index(X, method="PDScanning", backend="jax", schedule=_pol())
    r0 = sess.search(Q, K)
    r1 = sess.search(Q, K, deadline_s=1e6)
    np.testing.assert_array_equal(r0.ids, r1.ids)
    np.testing.assert_array_equal(r0.dists, r1.dists)
    assert (r1.stats.extra[EXTRA_COVERAGE] == 1.0).all()
    assert not r1.stats.extra[EXTRA_UNCERTIFIED_MASK].any()


def test_pdx_anytime_expiry_withdraws_certificate():
    from repro.testing import faults
    X, Q = _decayed(2048, 96, seed=19)
    pol = _pol(row_block=256, anytime_block_group=1)
    sess = open_index(X, method="PDScanning", backend="jax", schedule=pol)
    sess.search(Q, K)                       # warm the jit cache
    with faults.inject(slow_block_s=0.05):
        res = sess.search(Q, K, deadline_s=0.01)
    cov = res.stats.extra[EXTRA_COVERAGE]
    assert (cov < 1.0).all() and (cov > 0.0).all()
    assert res.stats.extra[EXTRA_UNCERTIFIED_MASK].all()


def test_pdx_delta_segment_matches_merged():
    X, Q = _decayed(1100, 96, seed=23)
    Xnew = X[:64] * 1.01
    sess = open_index(X[64:], method="PDScanning", backend="jax",
                      schedule=_pol())
    sess.search(Q, K)                       # materialize the main layout
    sess.add(Xnew)
    assert sess.last_write_mode == "delta"  # grouped layout kept, delta added
    r_delta = sess.search(Q, K)
    merged = open_index(np.concatenate([X[64:], Xnew]), method="PDScanning",
                        backend="jax", schedule=_pol())
    r_full = merged.search(Q, K)
    np.testing.assert_array_equal(r_delta.ids, r_full.ids)
    np.testing.assert_allclose(r_delta.dists, r_full.dists,
                               rtol=1e-5, atol=1e-5)


def test_pdx_kernel_path_matches_jnp():
    X, Q = _decayed(800, 96, seed=29)
    base = _cfg(48, row_block=256, block_capacity=256, dim_groups=4)
    dj, ij, *_ = _run(X, Q, base)
    dk, ik, sk, pk, dmk, rk, _ = _run(X, Q, dataclasses.replace(
        base, use_kernel=True))
    np.testing.assert_array_equal(ij, ik)
    np.testing.assert_allclose(dj, dk, rtol=1e-5, atol=1e-5)
    bd, bi = _brute(X, Q, K)
    np.testing.assert_array_equal(ik, bi)
