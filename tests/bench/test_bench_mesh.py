"""A four-chip cell, whose corpus is divided over its chips, run end to end
through ``bench/run.py``'s code path on four CPU devices (in a process of
its own, as ``XLA_FLAGS`` must be set before JAX starts): the run is
correct, the corpus is divided over the four devices, and the served ids
equal those of the same configuration on one device."""
import json
import os
import subprocess
import sys

import bench_testutil

ROOT = bench_testutil.ROOT
SEED = 2**31 + 4242
ONE = "wiki768-1m.id-bulk"
FOUR = "wiki768-1m-4.id-bulk"

CHILD = r"""
import json, sys
root, one, four, seed = sys.argv[1], sys.argv[2], sys.argv[3], int(sys.argv[4])
sys.path[:0] = [sys.argv[5], sys.argv[5] + "/src"]
import jax
from bench import manifest
from bench.run import prepare, run_cell

out = {"devices": len(jax.devices()), "problems": manifest.problems(root),
       "run": run_cell(root, four, seed, 1.0, False, require_tpu=False,
                       cache=False)}
for cell in (four, one):
    p = prepare(root, cell, seed, require_tpu=False, cache=False)
    try:
        mesh = p.svc.session.backend.mesh
        tickets = [p.svc.submit(q) for q in p.pool[:64]]
        while p.svc.pending:
            p.svc.step()
        out[cell] = {
            "mesh": None if mesh is None else dict(mesh.shape),
            "status": sorted({t.status for t in tickets}),
            "certified": all(t.certified for t in tickets),
            "ids": [t.ids.tolist() for t in tickets]}
    finally:
        p.clock.close()
print(json.dumps(out))
"""


def four_chip_root(dest):
    """The tiny benchmark with a copy of ``wiki768-1m`` in a four-chip cell
    of its own."""
    root = bench_testutil.tiny_root(dest)
    cfg = (root / "bench/configs/wiki768-1m.json").read_text()
    (root / "bench/configs/wiki768-1m-4.json").write_text(cfg)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "wiki768-1m-4", "source": "a test",
                             "file": "bench/configs/wiki768-1m-4.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": FOUR, "config": "wiki768-1m-4",
                               "traffic": "id-bulk", "chips": 4,
                               "why": "a test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if ONE in m.get("workloads", []):
            m["workloads"].append(FOUR)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_sharded_cell_runs_correct_on_four_cpu_devices(tmp_path):
    root = four_chip_root(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", CHILD, str(root), ONE, FOUR, str(SEED),
         str(ROOT)], env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4 and out["problems"] == []
    run = out["run"]
    assert run["correct"] is True, run["checks"]
    assert run["failed"] == 0 and run["attempted"] > 0
    assert run["device"]["count"] == 4
    assert len(run["device"]["memory_peak_bytes_per_chip"]) == 4
    assert out[FOUR]["mesh"] == {"data": 4} and out[ONE]["mesh"] is None
    for cell in (FOUR, ONE):
        assert out[cell]["status"] == ["done"] and out[cell]["certified"]
    assert out[FOUR]["ids"] == out[ONE]["ids"]
