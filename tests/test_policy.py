"""Adaptive DCO policy engine coverage (core.policy, both engines, facade).

The contract under test (DESIGN.md §5): adaptive mode never changes exact-rule
results (fallback and repair only ADD scanned dims), an OOD batch provably
triggers the fallback while matching fdscan exactly, the verify-and-repair
guard fixes the capacity-overflow miss PR 2's certificate could only flag,
and both backends report the same telemetry keys."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import SchedulePolicy, open_index
from repro.core.engine import (EXTRA_EST_SAVED_FLOPS, EXTRA_FALLBACK_BLOCKS,
                               EXTRA_RULE_TIMELINE, EXTRA_SCREEN_PASS_MEAN,
                               EXTRA_SURVIVORS_MEAN,
                               EXTRA_UNCERTIFIED_QUERIES)
from repro.core.jax_engine import DcoEngineConfig
from repro.core.policy import HostPolicy, PolicyConfig, pass_threshold
from repro.core.stream_engine import stream_topk
from repro.vecdata.synthetic import make_ood_queries, recall_at_k

K = 10

ADAPTIVE_KEYS = (EXTRA_FALLBACK_BLOCKS, EXTRA_EST_SAVED_FLOPS,
                 EXTRA_RULE_TIMELINE)


def _policy(**kw):
    base = dict(d1=48, query_chunk=8, capacity=512, row_block=512,
                block_capacity=128)
    base.update(kw)
    return SchedulePolicy(**base)


def _gt(X, Q, k=K):
    d2 = (X ** 2).sum(1)[None, :] - 2.0 * Q @ X.T + (Q ** 2).sum(1)[:, None]
    return np.argsort(d2, axis=1)[:, :k]


# ---------------------------------------------------------------------------
# cost model + host decision unit tests
# ---------------------------------------------------------------------------

def test_pass_threshold_cost_model():
    """Threshold falls with margin, vanishes when screening can't pay."""
    t1 = pass_threshold(200, 48, 152, 1.0, 8.0)
    t2 = pass_threshold(200, 48, 152, 1.3, 8.0)
    assert 0.0 < t2 < t1 < 1.0
    # screening width ~ D: can never pay -> always-fallback threshold
    assert pass_threshold(200, 196, 4, 1.1, 8.0) <= 0.0
    # nearly-free screen with cheap completion: never falls back
    assert pass_threshold(200, 1, 10, 1.0, 0.0) >= 1.0


def test_host_policy_hysteresis_and_recovery():
    """Mode enters above the threshold, exits only below the hysteresis
    band, and the telemetry counts what was actually served."""
    cfg = PolicyConfig(fallback_margin=1.0, ewma_alpha=1.0, overhead_dims=0.0,
                       hysteresis=0.5)
    hp = HostPolicy(cfg, D=100)
    thr = pass_threshold(100, 10, 100, 1.0, 0.0)      # 0.9
    hp.observe(100, 95, 10.0)                         # frac 0.95 > thr
    assert hp.mode
    hp.observe(100, 60, 10.0)     # 0.6 > thr*hyst=0.45 -> stays in fallback
    assert hp.mode
    hp.observe(100, 20, 10.0)                         # 0.2 < 0.45 -> recovers
    assert not hp.mode
    hp.block_served(True, 100, 100, 10.0)
    hp.block_served(False, 100, 5, 10.0)
    assert hp.fallback_blocks == 1 and hp.timeline == [True, False]


# ---------------------------------------------------------------------------
# jax streaming engine
# ---------------------------------------------------------------------------

def test_adaptive_bit_identical_on_id_queries(sift_small):
    """Acceptance: on exact rules with in-distribution queries the adaptive
    session returns bit-identical ids AND distances to the fixed session,
    and the policy never fires."""
    ds = sift_small
    r0 = open_index(ds.X, index="flat", method="PDScanning+", backend="jax",
                    schedule=_policy()).search(ds.Q[:8], K)
    r1 = open_index(ds.X, index="flat", method="PDScanning+", backend="jax",
                    schedule=_policy(adaptive=True)).search(ds.Q[:8], K)
    np.testing.assert_array_equal(r0.ids, r1.ids)
    np.testing.assert_array_equal(r0.dists, r1.dists)
    assert r1.stats.extra[EXTRA_FALLBACK_BLOCKS] == 0.0
    assert all(v == 0.0 for v in r1.stats.extra[EXTRA_RULE_TIMELINE])
    assert r1.stats.extra[EXTRA_EST_SAVED_FLOPS] > 0.0


def test_adaptive_ood_triggers_fallback_and_matches_fdscan(sift_small):
    """Acceptance: an adversarial OOD batch provably triggers the fallback
    (fallback_blocks > 0) while still matching fdscan ids exactly; the same
    batch through the fixed rule is flagged uncertified."""
    ds = sift_small
    Qo = make_ood_queries(ds.X, 8, severity=1.0)
    ra = open_index(ds.X, index="flat", method="PDScanning+", backend="jax",
                    schedule=_policy(adaptive=True)).search(Qo, K)
    assert ra.stats.extra[EXTRA_FALLBACK_BLOCKS] > 0
    assert ra.stats.extra[EXTRA_UNCERTIFIED_QUERIES] == 0.0
    rf = open_index(ds.X, index="flat", method="FDScanning", backend="jax",
                    schedule=_policy()).search(Qo, K)
    np.testing.assert_array_equal(ra.ids, rf.ids)
    assert recall_at_k(ra.ids, _gt(ds.X, Qo)) == 1.0
    # the fixed rule on the same batch overflows its completion budget and
    # cannot certify its answers — the situation the policy exists to avoid
    rfix = open_index(ds.X, index="flat", method="PDScanning+", backend="jax",
                      schedule=_policy()).search(Qo, K)
    assert rfix.stats.extra[EXTRA_UNCERTIFIED_QUERIES] > 0.0


def test_adaptive_repairs_capacity_overflow_miss():
    """The verify-and-repair guard: the adversarial decoy corpus of
    tests/test_stream_engine.py (capacity overflow pushes the true neighbor
    out of the completion budget) is a flagged MISS for the fixed engine —
    the adaptive engine must re-complete the unsafe block and return the
    exact answer with an intact certificate."""
    rng = np.random.default_rng(0)
    n, D, d1, k = 4096, 128, 48, 10
    X = rng.standard_normal((n, D)).astype(np.float32) * 4.0
    q = np.zeros(D, np.float32)
    X[:300, :d1] = rng.standard_normal((300, d1)).astype(np.float32) / 8.0
    X[:300, d1:] = 0.0
    X[:300, d1] = 10.0
    X[300] = 0.0
    X[300, 0] = 2.0
    st = {"x_lead": jnp.asarray(X[:, :d1]), "x_tail": jnp.asarray(X[:, d1:]),
          "lead_sq": jnp.asarray((X[:, :d1] ** 2).sum(1)),
          "tail_sq": jnp.asarray((X[:, d1:] ** 2).sum(1))}
    ql, qt = jnp.asarray(q[None, :d1]), jnp.asarray(q[None, d1:])
    cfg = DcoEngineConfig(kind="lb", d1=d1, k=k, query_chunk=1,
                          row_block=4096, block_capacity=128,
                          use_kernel=False)
    d0, i0, _, _, dm0, _, _ = stream_topk(st, ql, qt, cfg)
    assert 300 not in np.asarray(i0)[0]              # fixed engine: miss...
    assert float(dm0[0]) <= float(d0[0, -1])         # ...flagged, not fixed
    cfga = dataclasses.replace(cfg, policy=PolicyConfig())
    d1_, i1, s1, p1, dm1, _, _, rep = stream_topk(st, ql, qt, cfga)
    assert np.asarray(i1)[0, 0] == 300 and float(d1_[0, 0]) == 4.0
    assert not np.isfinite(float(dm1[0]))            # repaired: nothing dropped
    assert float(np.asarray(rep["fallback_blocks"])[0]) > 0


def test_adaptive_ragged_batch_matches_aligned(sift_small):
    """Padding queries must not perturb chunk-level decisions or results."""
    ds = sift_small
    sess = open_index(ds.X, index="flat", method="PDScanning+", backend="jax",
                      schedule=_policy(query_chunk=4, adaptive=True))
    r_full = sess.search(ds.Q[:8], K)
    r_ragged = sess.search(ds.Q[:7], K)
    assert r_ragged.ids.shape == (7, K)
    np.testing.assert_array_equal(r_ragged.ids, r_full.ids[:7])


def test_adaptive_estimator_rule_stays_reasonable(sift_small):
    """Estimator rules under the policy: the fallback can only add exactly
    completed rows, so OOD recall must not fall below the fixed rule's."""
    ds = sift_small
    Qo = make_ood_queries(ds.X, 8, severity=1.0)
    gt = _gt(ds.X, Qo)
    rfix = open_index(ds.X, index="flat", method="DADE", backend="jax",
                      schedule=_policy()).search(Qo, K)
    rada = open_index(ds.X, index="flat", method="DADE", backend="jax",
                      schedule=_policy(adaptive=True)).search(Qo, K)
    assert recall_at_k(rada.ids, gt) >= recall_at_k(rfix.ids, gt)
    assert rada.stats.extra[EXTRA_FALLBACK_BLOCKS] > 0


def test_adaptive_mesh_rejected(sift_small):
    import jax
    from jax.sharding import Mesh
    ds = sift_small
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    with pytest.raises(ValueError, match="single-device"):
        open_index(ds.X[:512], index="flat", method="PDScanning+",
                   backend="jax", mesh=mesh,
                   schedule=_policy(adaptive=True))


# ---------------------------------------------------------------------------
# host engine + cross-backend telemetry
# ---------------------------------------------------------------------------

def test_adaptive_telemetry_present_on_both_backends(sift_small):
    """Both backends report the canonical extra keys with the same names
    (api.types.STAT_EXTRA_KEYS) so host and jax runs are comparable."""
    ds = sift_small
    Qo = make_ood_queries(ds.X, 8, severity=1.0)
    for backend in ("host", "jax"):
        res = open_index(ds.X, index="flat", method="PDScanning+",
                         backend=backend,
                         schedule=_policy(adaptive=True)).search(Qo, K)
        ex = res.stats.extra
        for key in ADAPTIVE_KEYS + (EXTRA_SURVIVORS_MEAN,
                                    EXTRA_SCREEN_PASS_MEAN,
                                    EXTRA_UNCERTIFIED_QUERIES):
            assert key in ex, (backend, key)
        assert ex[EXTRA_FALLBACK_BLOCKS] > 0, backend
        assert isinstance(ex[EXTRA_RULE_TIMELINE], list)
        assert recall_at_k(res.ids, _gt(ds.X, Qo)) == 1.0, backend


def test_host_adaptive_identical_results_and_ivf(sift_small):
    """Host fallback only ever adds scanned dims, so flat AND IVF results
    are identical with the policy on; the shadow screen's extra dims are
    charged to dims_scanned."""
    ds = sift_small
    Qo = make_ood_queries(ds.X, 6, severity=1.0)
    for index in ("flat", "ivf"):
        # full probe on ivf: enough candidate blocks for the host policy's
        # history-based decision to engage
        r0 = open_index(ds.X, index=index, method="PDScanning+",
                        backend="host",
                        schedule=_policy()).search(Qo, K, nprobe=64)
        r1 = open_index(ds.X, index=index, method="PDScanning+",
                        backend="host",
                        schedule=_policy(adaptive=True)).search(Qo, K, nprobe=64)
        np.testing.assert_array_equal(r0.ids, r1.ids), index
        assert r1.stats.extra[EXTRA_FALLBACK_BLOCKS] > 0, index
        assert len(r1.stats.extra[EXTRA_RULE_TIMELINE]) > 0, index
