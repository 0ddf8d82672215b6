"""bench/run.py end to end on the CPU at a tiny size: a result that is
correct, the control that is not, a cell added as files, and no result
without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import bench_testutil
import numpy as np
import pytest

from bench import reference
from bench.run import run_cell

ROOT = bench_testutil.ROOT
SEED = 2**31 + 12345


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return bench_testutil.tiny_root(tmp_path_factory.mktemp("tiny"))


def _run(root, cell, **kw):
    return run_cell(root, cell, SEED, 1.0, False, require_tpu=False,
                    cache=False, **kw)


@pytest.mark.parametrize("cell", ["wiki768-1m.id-bulk",
                                  "laion512-2m.ood-bulk"])
def test_cell_runs_correct_on_the_cpu(tiny, cell):
    out = _run(tiny, cell)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    names = {m["name"] for m in json.loads(
        (tiny / "BENCHMARK.json").read_text())["end_to_end"]
        if cell in m.get("workloads", [cell])}
    assert set(out["metrics"]) == names
    assert list(out)[-1] == "checks"
    assert all(v["value"] > 0 for v in out["metrics"].values())


def test_control_comes_out_not_correct(tiny):
    """The cells' control is ``high`` (three bf16 passes), which a CPU
    computes as full float32; here the rung below it stands in, through
    the same harness path that the chip's control runs take."""
    out = _run(tiny, "wiki768-1m.id-bulk", control="bfloat16")
    assert out["correct"] is False
    c = out["checks"]
    assert (c["wrong_ranks"]["value"] > c["wrong_ranks"]["limit"]
            or c["dist_err_ulps"]["value"] > c["dist_err_ulps"]["limit"])


def test_control_is_the_precision_below_the_configuration():
    import jax
    import jax.numpy as jnp
    for c in json.loads((ROOT / "BENCHMARK.json").read_text())["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["precision"].startswith("float32, products at HIGHEST")
        assert cfg["control_precision"] == "high"
    q = jnp.ones((2, 8), jnp.float32)
    for prec, want in (("highest", "HIGHEST"), ("high", "HIGH")):
        text = str(jax.make_jaxpr(
            lambda a, b, p=prec: reference.matmul(a, b, p))(q, q))
        assert f"Precision.{want}" in text


def test_reference_is_the_float64_brute_force():
    rng = np.random.default_rng(0)
    X = rng.standard_normal((3000, 48)).astype(np.float32)
    Q = rng.standard_normal((20, 48)).astype(np.float32)
    ids, d = reference.reference_topk(X, Q, 10)
    full = ((X[None].astype(np.float64) - Q[:, None]) ** 2).sum(-1)
    np.testing.assert_array_equal(ids, np.argsort(full, 1)[:, :10])
    np.testing.assert_allclose(d, np.sort(full, 1)[:, :10])
    nums = reference.compare(ids, d.astype(np.float32), X, Q, ids, d)
    assert nums["wrong_ranks"] == 0 and nums["dist_err_ulps"] < 1


def test_compare_counts_repeats_invalid_and_far_ids():
    rng = np.random.default_rng(1)
    X = rng.standard_normal((500, 16)).astype(np.float32)
    Q = rng.standard_normal((4, 16)).astype(np.float32)
    ids, d = reference.reference_topk(X, Q, 5)
    bad = ids.copy()
    bad[0, 1] = bad[0, 0]          # repeated: the whole query is wrong
    bad[1, 4] = -1                 # invalid
    far = ((X - Q[2]) ** 2).sum(1).argmax()
    bad[2, 0] = far                # the farthest row at rank 0
    nums = reference.compare(bad, d, X, Q, ids, d)
    assert nums["wrong_ranks"] == 5 + 1 + 1


def test_new_cell_is_new_files_and_entries(tiny, tmp_path):
    """A configuration, an open-loop traffic mix, a per-layer metric and a
    cell come in as new files and new entries; no file changes but
    BENCHMARK.json, which only gains entries."""
    root = tmp_path / "grown"
    shutil.copytree(tiny, root)
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    cfg = json.loads((root / "bench/configs/wiki768-1m.json").read_text())
    cfg["data"].update(dim=320, spectrum_alpha=0.8)
    (root / "bench/configs/tiny320.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/id-poisson.json").write_text(json.dumps(
        {"loop": "open", "arrival": "poisson", "rate_qps": 200,
         "queries": "id", "slots": 16}))
    (root / "bench/metrics/facade.step_ms.online.py").write_text(
        (root / "bench/metrics/facade.step_ms.bulk.py").read_text())
    bench = json.loads((root / "BENCHMARK.json").read_text())
    old = json.loads(json.dumps(bench))
    cell = "tiny320.id-poisson"
    bench["configs"].append({"name": "tiny320", "source": "a test",
                             "file": "bench/configs/tiny320.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": cell, "config": "tiny320",
                               "traffic": "id-poisson", "chips": 1,
                               "why": "a test"})
    for name in ("p50_ms", "p99_ms"):
        bench["end_to_end"].append(
            {"name": name, "unit": "ms", "better": "lower", "bound": 0.1,
             "source": "host_clock", "workloads": [cell]})
    bench["per_layer"].append(
        {"name": "facade.step_ms.online", "unit": "ms", "better": "lower",
         "source": "program_span", "layer": "facade", "moves": "p99_ms",
         "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        assert bench[key][:len(old[key])] == old[key]   # entries only added
    for p, data in before.items():
        if p.name != "BENCHMARK.json":
            assert p.read_bytes() == data
    from bench import manifest
    assert manifest.problems(root) == []
    out = _run(root, cell)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"qps", "setup_s", "p50_ms", "p99_ms"}
    assert out["metrics"]["p99_ms"]["value"] >= out["metrics"]["p50_ms"]["value"]


def _cli(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, script, "--workload", "wiki768-1m.id-bulk",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    assert proc.returncode != 0
    lines = proc.stdout.strip().splitlines()
    assert not lines or not lines[-1].lstrip().startswith("{")


def test_no_tpu_no_result():
    proc = _cli(ROOT, "bench/run.py")
    _no_result(proc)
    assert "no TPU" in proc.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    _no_result(_cli(tmp_path, "bench/run.py"))
