"""Streaming engine (core.stream_engine) coverage: parity vs the two-stage
engine and the host scan across all decision rules, kernel-vs-jnp path
identity, ragged query batches, ragged corpus blocks, k > capacity, and the
device-side IVF probe path."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import SchedulePolicy, open_index
from repro.core.engine import make_schedule
from repro.core.jax_engine import (DcoEngineConfig, build_device_state,
                                   two_stage_topk)
from repro.core.methods import make_method
from repro.core.stream_engine import stream_topk
from repro.vecdata.synthetic import recall_at_k

K = 10

#: facade method -> engine decision rule it exercises (all six dco_scan
#: rules plus DDCopq's PQ rule, which only the streaming engine serves)
RULES = {"FDScanning": "fdscan", "PDScanning+": "lb",
         "ADSampling": "adsampling", "DADE": "dade",
         "DDCres": "ddcres", "DDCpca": "ratio", "DDCopq": "opq"}


def _fitted(ds, name):
    m = make_method(name).fit(ds.X)
    if m.needs_training:
        rng = np.random.default_rng(7)
        m.train(ds.X[rng.choice(ds.n, 24)], K, make_schedule(ds.dim))
    return m


def _policy(**kw):
    base = dict(d1=48, query_chunk=8, capacity=512, row_block=512,
                block_capacity=128)
    base.update(kw)
    return SchedulePolicy(**base)


@pytest.mark.parametrize("kind", ["lb", "fdscan"])
def test_stream_bit_identical_to_two_stage_on_exact_rules(kind, sift_small):
    """Acceptance: on exact rules the streaming engine returns the same
    top-k ids as the two-stage engine, and the same squared distances up to
    f32 rounding.  The engines sum the cancelling form
    ||x||^2 - 2 x.q + ||q||^2 in different orders, so the distances may
    differ by a few ulps of the norms that cancel — not of the distance."""
    ds = sift_small
    m = make_method("PDScanning+").fit(ds.X)
    cfg = DcoEngineConfig(kind=kind, d1=48, k=K, capacity=512, query_chunk=8,
                          row_block=512, block_capacity=128, use_kernel=False)
    st = build_device_state(m, cfg.d1)
    Q = jnp.asarray(ds.Q[:8]) @ jnp.asarray(m.state["pca"]["W"])
    d0, i0, _ = two_stage_topk(st, Q[:, :cfg.d1], Q[:, cfg.d1:], cfg)
    d1_, i1, s1, p1, dm1, _ = stream_topk(st, Q[:, :cfg.d1], Q[:, cfg.d1:], cfg)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    norms = (ds.X ** 2).sum(1).max() + (ds.Q[:8] ** 2).sum(1).max()
    atol = 8 * np.finfo(np.float32).eps * norms
    np.testing.assert_allclose(np.asarray(d0), np.asarray(d1_), rtol=0,
                               atol=atol)
    assert (np.asarray(s1) > 0).all() and (np.asarray(p1) >= np.asarray(s1)).all()


def test_stream_all_rules_facade_parity(sift_small):
    """Every decision rule through the facade: exact rules match the host
    backend exactly; estimator rules hold the same recall bar the host path
    is tested at elsewhere."""
    ds = sift_small
    gt, _ = ds.ground_truth(K)
    for name, kind in RULES.items():
        rh = open_index(ds.X, index="flat", method=name, backend="host",
                        schedule=_policy()).search(ds.Q[:8], K)
        rj = open_index(ds.X, index="flat", method=name, backend="jax",
                        schedule=_policy()).search(ds.Q[:8], K)
        if kind in ("lb", "fdscan"):
            np.testing.assert_array_equal(rh.ids, rj.ids), name
        rec = recall_at_k(rj.ids, gt[:8])
        assert rec >= 0.9, (name, rec)
        if kind not in ("fdscan",):
            assert rj.stats.dims_scanned < rj.stats.dims_total, name


def test_stream_kernel_path_matches_jnp_path(sift_small):
    """The Pallas kernel (interpret mode here, compiled on TPU) and the jnp
    block path make identical screening decisions -> identical top-k."""
    ds = sift_small
    for name in ("PDScanning+", "ADSampling", "DDCopq"):
        m = _fitted(ds, name)
        dstate = m.device_state()
        kw = dict(kind=dstate["kind"], d1=48, k=K, query_chunk=8,
                  row_block=512, block_capacity=128)
        if dstate["kind"] == "opq":
            kw["theta"] = dstate["theta"]
        if dstate["kind"] == "adsampling":
            kw["eps0"] = dstate["eps0"]
        cfg = DcoEngineConfig(**kw, use_kernel=False)
        st = build_device_state(dstate, cfg.d1)
        if dstate["kind"] == "opq":
            st["codes"] = jnp.asarray(np.asarray(dstate["codes"]), jnp.int32)
        W = dstate.get("W")
        Q = np.asarray(ds.Q[:8] @ W if W is not None else ds.Q[:8], np.float32)
        qe = {}
        if dstate["kind"] == "opq":
            from repro.core import transforms as T
            pq = {"books": dstate["books"], "splits": dstate["splits"]}
            qe = {"lut": jnp.asarray(np.stack([T.pq_query_lut(pq, q)
                                               for q in Q]))}
        ql, qt = jnp.asarray(Q[:, :48]), jnp.asarray(Q[:, 48:])
        d0, i0, s0, p0, dm0, _ = stream_topk(st, ql, qt, cfg, qe)
        cfgk = dataclasses.replace(cfg, use_kernel=True)
        d1_, i1, s1, p1, dm1, _ = stream_topk(st, ql, qt, cfgk, qe)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1)), name
        np.testing.assert_array_equal(np.asarray(p0), np.asarray(p1)), name


def test_stream_ragged_query_batch(sift_small):
    """nq not a multiple of query_chunk pads and slices correctly."""
    ds = sift_small
    sess = open_index(ds.X, index="flat", method="PDScanning+", backend="jax",
                      schedule=_policy(query_chunk=4))
    r_full = sess.search(ds.Q[:8], K)           # aligned: 8 % 4 == 0
    r_ragged = sess.search(ds.Q[:7], K)         # ragged: 7 % 4 != 0
    assert r_ragged.ids.shape == (7, K)
    np.testing.assert_array_equal(r_ragged.ids, r_full.ids[:7])


def test_stream_corpus_not_multiple_of_row_block(sift_small):
    """N % row_block != 0: padding rows must never surface in the top-k."""
    ds = sift_small                              # 5000 rows
    m = make_method("PDScanning+").fit(ds.X)
    gt, _ = ds.ground_truth(K)
    Q = jnp.asarray(ds.Q[:8]) @ jnp.asarray(m.state["pca"]["W"])
    for rb in (384, 512, 4999, 8192):            # ragged, even, near-N, > N
        cfg = DcoEngineConfig(kind="lb", d1=48, k=K, query_chunk=8,
                              row_block=rb, block_capacity=128,
                              use_kernel=False)
        st = build_device_state(m, cfg.d1)
        d, i, s, p, dm, _ = stream_topk(st, Q[:, :cfg.d1], Q[:, cfg.d1:], cfg)
        assert (np.asarray(i) >= 0).all() and (np.asarray(i) < ds.n).all()
        assert recall_at_k(np.asarray(i), gt[:8]) == 1.0, rb


def test_stream_k_exceeds_block_capacity(sift_small):
    """k > block_capacity still returns a well-formed (and here complete)
    top-k: each block contributes at most block_capacity candidates but the
    carried top-k accumulates across blocks."""
    ds = sift_small
    m = make_method("PDScanning+").fit(ds.X)
    k = 32
    cfg = DcoEngineConfig(kind="lb", d1=48, k=k, query_chunk=8,
                          row_block=512, block_capacity=16, use_kernel=False)
    st = build_device_state(m, cfg.d1)
    Q = jnp.asarray(ds.Q[:8]) @ jnp.asarray(m.state["pca"]["W"])
    d, i, s, p, dm, _ = stream_topk(st, Q[:, :cfg.d1], Q[:, cfg.d1:], cfg)
    assert d.shape == (8, k) and np.isfinite(np.asarray(d)).all()
    assert (np.diff(np.asarray(d), axis=1) >= 0).all()      # sorted ascending
    gt, _ = ds.ground_truth(k)
    assert recall_at_k(np.asarray(i), gt[:8]) >= 0.95


def test_stream_truncation_is_certified():
    """Adversarial block-capacity overflow: many decoys with tiny stage-1
    lower bounds crowd the completion budget and push out the true
    neighbor.  The engine cannot avoid the (capacity-bounded) miss, but its
    exactness certificate MUST catch it: dropped_min_est <= kth distance.
    With a budget larger than the decoy set, the result is exact again and
    the certificate passes."""
    rng = np.random.default_rng(0)
    n, D, d1, k = 4096, 128, 48, 10
    X = rng.standard_normal((n, D)).astype(np.float32) * 4.0
    q = np.zeros(D, np.float32)
    # 300 decoys: lead distance ~1 (beats everyone at stage 1), tail huge
    X[:300, :d1] = rng.standard_normal((300, d1)).astype(np.float32) / 8.0
    X[:300, d1:] = 0.0
    X[:300, d1] = 10.0
    # true nearest neighbor: lead distance ~2, zero tail
    X[300] = 0.0
    X[300, 0] = 2.0
    st = {"x_lead": jnp.asarray(X[:, :d1]), "x_tail": jnp.asarray(X[:, d1:]),
          "lead_sq": jnp.asarray((X[:, :d1] ** 2).sum(1)),
          "tail_sq": jnp.asarray((X[:, d1:] ** 2).sum(1))}
    ql = jnp.asarray(q[None, :d1])
    qt = jnp.asarray(q[None, d1:])
    cfg = DcoEngineConfig(kind="lb", d1=d1, k=k, query_chunk=1,
                          row_block=4096, block_capacity=128,
                          use_kernel=False)
    d, i, s, p, dm, _ = stream_topk(st, ql, qt, cfg)
    assert 300 not in np.asarray(i)[0]                   # NN was truncated...
    assert float(dm[0]) <= float(d[0, -1])               # ...and flagged
    cfg2 = dataclasses.replace(cfg, block_capacity=512)  # budget > decoys
    d2, i2, s2, p2, dm2, _ = stream_topk(st, ql, qt, cfg2)
    assert np.asarray(i2)[0, 0] == 300 and float(d2[0, 0]) == 4.0
    assert float(dm2[0]) > float(d2[0, -1])              # certified exact


def test_jax_ivf_probe_matches_host(sift_small):
    """Device-side IVF probing selects the same partitions as the host index
    and completes the same exact top-k; recall grows with nprobe and hits
    1.0 at full probe."""
    ds = sift_small
    gt, _ = ds.ground_truth(K)
    params = {"n_list": 32}
    sh = open_index(ds.X, index="ivf", method="PDScanning+", backend="host",
                    schedule=_policy(), index_params=params)
    sj = open_index(ds.X, index="ivf", method="PDScanning+", backend="jax",
                    schedule=_policy(), index_params=params)
    recs = []
    for nprobe in (2, 8, 32):
        a = sh.search(ds.Q[:8], K, nprobe=nprobe)
        b = sj.search(ds.Q[:8], K, nprobe=nprobe)
        np.testing.assert_array_equal(a.ids, b.ids), nprobe
        assert b.stats.dims_scanned < b.stats.dims_total
        recs.append(recall_at_k(b.ids, gt[:8]))
    assert recs[0] <= recs[1] <= recs[2] == 1.0


def test_jax_ivf_rejects_mesh(sift_small):
    import jax
    from jax.sharding import Mesh
    ds = sift_small
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    with pytest.raises(ValueError, match="single-device"):
        open_index(ds.X[:512], index="ivf", method="PDScanning+",
                   backend="jax", mesh=mesh)


def test_stream_survivor_stats_are_real(sift_small):
    """survivors_mean reflects actual stage-2 completions (bounded by what
    the running tau admits), not a capacity bound."""
    ds = sift_small
    res = open_index(ds.X, index="flat", method="PDScanning+", backend="jax",
                     schedule=_policy()).search(ds.Q[:8], K)
    sm = res.stats.extra["survivors_mean"]
    assert 0 < sm < ds.n
    assert sm != min(512, ds.n)          # not the old capacity upper bound
    assert res.stats.extra["screen_pass_mean"] >= sm
    assert res.stats.extra["uncertified_queries"] == 0.0
