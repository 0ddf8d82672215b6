"""The mesh path of the served search (``open_index(..., mesh=)`` ->
``JaxBackend`` -> ``make_distributed_topk`` over ``shard_stream_blocks``)
on four CPU devices, against a float64 brute force.

The devices exist only in a process started with ``XLA_FLAGS``, so one
child process serves every case and prints what it saw as JSON; each test
reads one part of it."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHILD = r'''
import dataclasses
import json
import numpy as np
import jax
from jax.sharding import Mesh

from repro.api import SchedulePolicy, open_index
from repro.core import jax_engine, stream_engine as se

K, D, D1, ROWS = 10, 64, 16, 4000       # 4 shards of 1,000 rows
POL = SchedulePolicy(d1=D1, row_block=256, block_capacity=64, query_chunk=8)
mesh = Mesh(np.asarray(jax.devices()[:4]), ("data",))
out = {"devices": len(jax.devices())}

builds = []
_shard = jax_engine.shard_stream_blocks
def counted(*a, **kw):
    builds.append(1)
    return _shard(*a, **kw)
jax_engine.shard_stream_blocks = counted


def brute(X, Q):
    d = ((Q[:, None].astype(np.float64) - X[None].astype(np.float64)) ** 2).sum(-1)
    ids = np.argsort(d, 1, kind="stable")[:, :K]
    return ids, np.take_along_axis(d, ids, 1)


def served(sess, X, Q):
    res = sess.search(Q, K)
    ids, dd = brute(X, Q)
    return {"ids_equal": bool((res.ids == ids).all()),
            "dist_err": float(np.abs(res.dists - dd).max() / dd.max()),
            "pad_ids": int((res.ids < 0).sum()),
            "certified": bool(not np.asarray(
                res.stats.extra["uncertified_mask"]).any())}, res


# a decaying spectrum, as embeddings have, so that the screen prunes and a
# block's survivors fit block_capacity in some blocks and not in others
rng = np.random.default_rng(7)
X = (rng.standard_normal((ROWS, D)) * 0.85 ** np.arange(D)).astype(np.float32)
Q = (rng.standard_normal((20, D)) * 0.85 ** np.arange(D)).astype(np.float32)
sess = open_index(X, index="flat", method="PDScanning+", backend="jax",
                  schedule=POL, mesh=mesh)
out["ragged"], res = served(sess, X, Q)
be = sess.backend
blk = be._blocks
out["layout"] = {"xl": list(blk["xl"].shape),
                 "pad_rows": int((np.asarray(blk["ids"]) < 0).sum()),
                 "row_block": be.engine_config(K).row_block,
                 "shards": sorted({s.data.shape[0] for s in
                                   blk["xl"].addressable_shards})}
tsq = np.asarray(blk["tsq"]).reshape(4, -1)
real = np.asarray(blk["ids"]).reshape(4, -1) >= 0
out["tail_min"] = [np.asarray(be._state["tail_min"]).tolist(),
                   [float(tsq[s][real[s]].min()) for s in range(4)]]

# one resident copy: the distinct buffers of every live device array, per
# device, against one shard's layout
bufs = {}
for a in jax.live_arrays():
    for s in a.addressable_shards:
        bufs.setdefault(s.device.id, {})[
            s.data.unsafe_buffer_pointer()] = s.data.nbytes
shard_bytes = sum(a.addressable_shards[0].data.nbytes for a in blk.values())
out["memory"] = {"shard_bytes": shard_bytes,
                 "per_device": [sum(bufs.get(d.id, {}).values())
                                for d in mesh.devices.flat]}

# the same layout and program serve every later call of a shape
cfg = be.engine_config(K)
for _ in range(3):
    sess.search(Q[:16], K)
sess.search(Q[:13], K)
out["reuse"] = {"builds": len(builds), "same_layout": be._blocks is blk,
                "programs": be._mesh_fns[cfg]._cache_size()}

# telemetry: what each shard's one-device scan counts, summed as the mesh
# sums it; DDCres also screens with each shard's least real tail norm
def telemetry(sess):
    be = sess.backend
    stats = sess.search(Q, K).stats.extra
    cfg = be.engine_config(K)
    xr = np.asarray(be._dstate["Xrot"], np.float32)
    ql, qt, qe = be._prep_queries(Q)
    qe = {key: jax.numpy.asarray(v) for key, v in qe.items()}
    passed = dims = shared = nblocks = 0
    for s in range(4):
        st = jax_engine.build_device_state(
            {"Xrot": xr[s * ROWS // 4:(s + 1) * ROWS // 4]}, D1)
        b = se.build_stream_blocks(st, cfg.row_block)
        o = se.stream_topk(st, ql, qt, cfg, qe, blocks=b)
        passed = passed + np.asarray(o[3], np.int64)
        dims = dims + np.asarray(o[5], np.float64)
        shared = shared + np.asarray(o[6], np.int64)
        nblocks += b["xl"].shape[0]
    return {"mesh": [stats["screen_pass_mean"], stats["dims_read_mean"],
                     stats["shared_block_share"]],
            "shards": [float(passed.mean()),
                       float(dims.sum()) / (len(Q) * ROWS),
                       float(shared.mean()) / nblocks]}


out["telemetry"] = {"PDScanning+": telemetry(sess), "DDCres": telemetry(
    open_index(X, index="flat", method="DDCres", backend="jax",
               schedule=POL, mesh=mesh))}

# decoys: rows on a shell around the mean, queries at the mean, where an
# unmasked zero pad row would be every query's nearest neighbour (all rows
# lie about equally far, so every block completes whole)
Xs = rng.standard_normal((ROWS, D)).astype(np.float32)
Xs = 10.0 * Xs / np.linalg.norm(Xs, axis=1, keepdims=True)
Qs = (Xs.mean(0) + 0.01 * rng.standard_normal((16, D))).astype(np.float32)
dec = open_index(Xs, index="flat", method="PDScanning+", backend="jax",
                 schedule=dataclasses.replace(POL, block_capacity=256),
                 mesh=mesh)
out["decoy"], _ = served(dec, Xs, Qs)
print(json.dumps(out))
'''


@pytest.fixture(scope="module")
def seen():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="src",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", CHILD], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4
    return out


def test_unaligned_shards_serve_the_brute_force(seen):
    """4 x 1,000 rows at row_block 256: each shard pads 24 rows into its
    fourth block, and the served answers are the float64 brute force's,
    every one certified."""
    assert seen["layout"] == {"xl": [16, 256, 16], "pad_rows": 4 * 24,
                              "row_block": 256, "shards": [4]}
    r = seen["ragged"]
    assert r["ids_equal"] and r["dist_err"] < 1e-5
    assert r["pad_ids"] == 0 and r["certified"]
    # the DDCres screen's bound is each shard's least tail norm over its
    # real rows, not the pad rows' zero
    kept, real = seen["tail_min"]
    assert kept == real and min(real) > 0


def test_pad_rows_are_never_answers(seen):
    """Queries at the corpus mean, where a zero pad row lies closer than
    any real row: the pad rows stay masked."""
    r = seen["decoy"]
    assert r["pad_ids"] == 0
    assert r["ids_equal"] and r["dist_err"] < 1e-5 and r["certified"]


@pytest.mark.parametrize("method", ["PDScanning+", "DDCres"])
def test_telemetry_is_the_shards_summed(seen, method):
    """screen_pass_mean, dims_read_mean and shared_block_share on the mesh
    equal each shard's one-device scan summed over the shards (float sums
    in another order aside).  Each shard starts its running tau afresh, so
    a one-device scan of the whole corpus, whose tau carries from block to
    block, counts fewer rows: the sum is what the mesh can match."""
    t = seen["telemetry"][method]
    mesh, shards = t["mesh"], t["shards"]
    assert mesh[0] == shards[0] and mesh[2] == shards[2]
    assert mesh[1] == pytest.approx(shards[1], rel=1e-6)
    assert 0.0 < mesh[2] < 1.0 and 0 < mesh[0] < 4000


def test_one_layout_and_one_program_per_batch_shape(seen):
    """Batches of 20, 16 (three times) and 13 queries: one layout, built
    at the first search, and one compiled program per batch shape."""
    r = seen["reuse"]
    assert r == {"builds": 1, "same_layout": True, "programs": 3}


def test_each_device_holds_one_copy_of_its_shard(seen):
    m = seen["memory"]
    assert len(m["per_device"]) == 4
    assert all(0 < b <= 1.1 * m["shard_bytes"] for b in m["per_device"])
