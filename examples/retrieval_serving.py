"""Distributed retrieval serving: the paper's engine on a device mesh,
through the facade.

Opens one session with ``backend="jax"`` and a host mesh: the corpus is
sharded over the mesh, queries are batch-rotated once, and each search runs
the certified streaming engine (running-tau block scan, DESIGN.md §4) per
shard with a global top-k merge — the production serving path the dry-run
lowers against 256/512 chips, here on 8 host devices.

  PYTHONPATH=src python examples/retrieval_serving.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import sys
import time

sys.path.insert(0, "src")

import numpy as np

from repro.api import SchedulePolicy, open_index
from repro.launch.mesh import make_host_mesh
from repro.vecdata import load_dataset
from repro.vecdata.synthetic import recall_at_k
from repro.utils.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ds = load_dataset("sift", scale=0.3)          # 30k x 128
    mesh = make_host_mesh(4, 2)
    sess = open_index(ds.X, index="flat", method="PDScanning+", backend="jax",
                      schedule=SchedulePolicy(d1=48, capacity=2048,
                                              query_chunk=8),
                      mesh=mesh)
    res = sess.search(ds.Q[:32], 10)              # compile + run
    t0 = time.perf_counter()
    for _ in range(5):
        res = sess.search(ds.Q[:32], 10)
    dt = (time.perf_counter() - t0) / 5
    gt, _ = ds.ground_truth(10)
    rec = recall_at_k(np.asarray(res.ids), gt[:32])
    print(f"mesh={dict(mesh.shape)}  corpus={ds.n}x{ds.dim}")
    print(f"batch=32 queries in {dt*1e3:.1f} ms  ({32/dt:.0f} QPS)  "
          f"recall@10={rec:.3f} (certified streaming scan, d1=48)")


if __name__ == "__main__":
    main()
