"""Index-level tests: IVF + HNSW recall, DCO-accelerated construction,
dynamic inserts, serving engine + DCO attention."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import QueryBatch, ScanStats, make_schedule
from repro.core.methods import make_method
from repro.search.hnsw import HNSWIndex
from repro.search.ivf import IVFIndex
from repro.vecdata.synthetic import recall_at_k

K = 10


def test_ivf_recall_vs_nprobe(sift_small):
    ds = sift_small
    idx = IVFIndex(n_list=64).build(ds.X)
    m = make_method("FDScanning").fit(ds.X)
    batch = QueryBatch.create(m, ds.Q[:16])
    gt, _ = ds.ground_truth(K)
    recs = []
    for nprobe in (2, 16, 64):
        found = [idx.search(m, batch, qi, K, nprobe)[1]
                 for qi in range(16)]
        recs.append(recall_at_k(np.array(found), gt[:16]))
    assert recs[-1] == 1.0                     # all partitions == brute force
    assert recs[0] <= recs[1] <= recs[2]


def test_ivf_dco_methods_agree_at_full_probe(sift_small):
    ds = sift_small
    idx = IVFIndex(n_list=32).build(ds.X)
    gt, _ = ds.ground_truth(K)
    for name in ("PDScanning+", "ADSampling", "DDCres"):
        m = make_method(name).fit(ds.X)
        stats = ScanStats()
        batch = QueryBatch.create(m, ds.Q[:8], stats=stats)
        found = [idx.search(m, batch, qi, K, 32)[1]
                 for qi in range(8)]
        rec = recall_at_k(np.array(found), gt[:8])
        assert rec >= 0.95, (name, rec)
        assert stats.pruning_ratio > 0.2


def test_ivf_insert(sift_small):
    ds = sift_small
    half = ds.n // 2
    idx = IVFIndex(n_list=32).build(ds.X[:half])
    m = make_method("PDScanning").fit(ds.X)
    cent_m = make_method("PDScanning").fit(idx.centroids)
    idx.insert(np.arange(half, ds.n), ds.X[half:], method=cent_m)
    assert idx.n == ds.n
    batch = QueryBatch.create(m, ds.Q[:8])
    gt, _ = ds.ground_truth(K)
    found = [idx.search(m, batch, qi, K, 32)[1] for qi in range(8)]
    assert recall_at_k(np.array(found), gt[:8]) == 1.0


@pytest.mark.slow
def test_hnsw_build_and_search():
    from repro.vecdata import load_dataset
    ds = load_dataset("sift", scale=0.02)       # 2k vectors
    sched = make_schedule(ds.dim)
    m = make_method("PDScanning+").fit(ds.X)
    idx = HNSWIndex(m=8, ef_construction=40).build(ds.X, method=m,
                                                   schedule=sched)
    batch = QueryBatch.create(m, ds.Q[:10], sched)
    gt, _ = ds.ground_truth(K)
    found = [idx.search(m, batch, qi, K, 90)[1]
             for qi in range(10)]
    rec = recall_at_k(np.array(found), gt[:10])
    assert rec >= 0.75, rec


def test_distributed_topk_subprocess():
    """shard_map engine == single-device engine (8 fake devices)."""
    import subprocess, sys, os
    code = '''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from repro.vecdata import load_dataset
from repro.core.methods import make_method
from repro.core.jax_engine import DcoEngineConfig, build_device_state, two_stage_topk, make_distributed_topk
from repro.launch.mesh import make_host_mesh
from jax.sharding import NamedSharding, PartitionSpec as P
ds = load_dataset("sift", scale=0.04)
m = make_method("PDScanning+").fit(ds.X)
cfg = DcoEngineConfig(kind="lb", d1=48, k=10, capacity=512, query_chunk=8)
W = jnp.asarray(m.state["pca"]["W"]); Q = jnp.asarray(ds.Q[:8]) @ W
st = build_device_state(m, cfg.d1)
d0, i0, _ = two_stage_topk(st, Q[:, :cfg.d1], Q[:, cfg.d1:], cfg)
mesh = make_host_mesh(4, 2)
xr = np.asarray(m.state["Xrot"], np.float32)
sh = NamedSharding(mesh, P(("data","model")))
a = [jax.device_put(v, sh) for v in (xr[:, :cfg.d1], xr[:, cfg.d1:], (xr[:, :cfg.d1]**2).sum(1), (xr[:, cfg.d1:]**2).sum(1))]
fn = make_distributed_topk(mesh, cfg)
dd, ii, ss, _, dm, _, _ = fn(*a, Q[:, :cfg.d1], Q[:, cfg.d1:], {})
assert float(np.abs(np.sort(np.array(dd),1) - np.sort(np.array(d0),1)).max()) < 1e-3
ss = np.array(ss)
assert (ss > 0).all() and (ss <= ds.n).all()      # real completions, all shards
assert (np.array(dm) > np.array(dd)[:, -1]).all() # exactness certified
# facade mesh path must serve rules with per-query extras / rule scalars
from repro.api import open_index, SchedulePolicy
from repro.vecdata.synthetic import recall_at_k
gt, _ = ds.ground_truth(10)
pol = SchedulePolicy(d1=48, capacity=512, query_chunk=8)
for name in ("DDCres", "DADE"):
    sess = open_index(ds.X, index="flat", method=name, backend="jax",
                      schedule=pol, mesh=mesh)
    res = sess.search(ds.Q[:13], 10)          # ragged through the mesh
    assert recall_at_k(res.ids, gt[:13]) >= 0.95, name
print("DIST_OK")
'''
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert "DIST_OK" in r.stdout, r.stderr[-2000:]


class _ShapeOnlyMesh:
    """Enough mesh for make_distributed_topk's build-time validation (which
    only reads ``mesh.shape``) — no devices needed to prove the fail-fast."""

    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


def test_distributed_topk_validates_shard_alignment():
    """The mesh certificate sharp edge fails at BUILD time: shard sizes
    that don't divide evenly, or aren't a row_block multiple (phantom
    padding rows would weaken the streaming dropped-estimate certificate),
    raise a named ValueError instead of silently degrading."""
    from repro.core.jax_engine import DcoEngineConfig, make_distributed_topk
    mesh = _ShapeOnlyMesh({"data": 4, "model": 2})
    cfg = DcoEngineConfig(kind="lb", d1=16, k=10, row_block=64)
    with pytest.raises(ValueError, match="do not shard evenly"):
        make_distributed_topk(mesh, cfg, n_rows=903)     # 903 % 8 != 0
    with pytest.raises(ValueError, match="row_block"):
        make_distributed_topk(mesh, cfg, n_rows=8 * 96)  # 96 % 64 != 0
    # success paths need a real mesh (shard_map construction checks it)
    from repro.launch.mesh import make_host_mesh
    real = make_host_mesh(1, 1)
    # aligned rows build fine with the stream engine
    make_distributed_topk(real, cfg, n_rows=128)
    # the two_stage engine has no streaming certificate: only even split
    # is required (no error for a 96-row shard under row_block=64)
    make_distributed_topk(real, cfg, n_rows=96, engine="two_stage")
    # n_rows=None preserves the old caller-beware behavior
    make_distributed_topk(real, cfg, n_rows=None)


def test_aligned_row_block_is_largest_safe_divisor():
    from repro.core.jax_engine import _aligned_row_block
    assert _aligned_row_block(96, 64) == 48      # largest divisor <= 64
    assert _aligned_row_block(128, 64) == 64     # already aligned
    assert _aligned_row_block(97, 64) == 1       # prime shard: worst case
    assert _aligned_row_block(10, 64) == 10      # block larger than shard
    for per_shard, rb in ((96, 64), (1000, 48), (7, 3)):
        got = _aligned_row_block(per_shard, rb)
        assert per_shard % got == 0 and 1 <= got <= rb


def test_dco_attention_close_to_exact():
    from repro.serving.dco_attention import (dco_decode_attention,
                                             exact_decode_attention,
                                             fit_key_rotation)
    rng = np.random.default_rng(0)
    B, S, Hkv, G, hd = 2, 256, 2, 2, 32
    H = Hkv * G
    # keys with decaying spectrum so PCA screening has signal
    scale = (np.arange(1, hd + 1) ** -0.7).astype(np.float32)
    k = (rng.standard_normal((B, S, Hkv, hd)) * scale).astype(np.float32)
    v = rng.standard_normal((B, S, Hkv, hd)).astype(np.float32)
    q = (rng.standard_normal((B, H, hd)) * scale).astype(np.float32)
    rot = jnp.asarray(fit_key_rotation(k.reshape(-1, hd)))
    k_rot = jnp.einsum("bshd,de->bshe", jnp.asarray(k), rot)
    exact = exact_decode_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), S)
    # q must be rotated consistently inside dco fn (it rotates internally)
    approx_hi = dco_decode_attention(jnp.asarray(q), k_rot, jnp.asarray(v),
                                     rot, S, d1=8, cap=S)      # cap=S: exact
    np.testing.assert_allclose(np.asarray(approx_hi), np.asarray(exact),
                               rtol=2e-2, atol=2e-2)
    approx = dco_decode_attention(jnp.asarray(q), k_rot, jnp.asarray(v),
                                  rot, S, d1=16, cap=96)
    err = np.abs(np.asarray(approx) - np.asarray(exact)).max()
    assert err < 0.25, err


def test_serving_engine_completes():
    from repro.configs import smoke_config
    from repro.models import build_model
    from repro.serving.engine import Request, ServingEngine
    cfg = smoke_config("olmo-1b")
    api = build_model(cfg, remat="none")
    params = api.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, 4), max_new=3)
            for i in range(5)]
    eng = ServingEngine(api, slots=2, max_len=32)
    out = eng.run(params, reqs)
    assert sorted(out) == [0, 1, 2, 3, 4]
    assert all(len(v) == 3 for v in out.values())
