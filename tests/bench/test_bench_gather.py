"""The mesh cell and its three readers: the gather's own time, the skew of
the shards' arrival at the gather, and the chunk-shared completion share
from ``search.finish``, on a constructed four-plane trace; and the
committed benchmark with the mesh cell in it."""
import json
from types import SimpleNamespace

import bench_testutil
import pytest

from bench import manifest, stages, tracing

ROOT = bench_testutil.ROOT
MS = 1_000_000          # ns
CELL = "wiki768-4m.id-bulk"
LEAD = "jit(local_fn)/shard_map/jit(_stream_topk_padded)/dco.scan/dco.lead/pallas_call"
GATHER = "jit(local_fn)/shard_map/dco.gather/all_gather"
MERGE = "jit(local_fn)/shard_map/dco.gather/top_k"
NEW = ("engine.gather_ms.bulk", "engine.gather_skew_ms.bulk",
       "facade.shared_block_share.bulk")


def read(name, tr):
    ctx = SimpleNamespace(trace=tr, config={}, traffic={}, window=None,
                          peaks=None)
    return manifest.reader(ROOT, name)(ctx)


def four_planes():
    """A 10 ms window of two whole steps on four chips.  Each chip scans,
    then gathers (0.5 ms of all_gather, 0.25 ms of top_k).  Step 1: the
    chips reach the gather at 2, 2.5, 3 and 2 ms; step 2: at 6, 6, 7 and
    6.5 ms.  A fifth plane ran nothing."""
    reach = {0: (2, 6), 1: (2.5, 6), 2: (3, 7), 3: (2, 6.5)}
    tr = stages.ProgramTrace()
    for c, (r1, r2) in reach.items():
        ops, names = [], []
        for t0, r in ((1, r1), (5.5, r2)):
            ops += [("dco_scan.4", t0, r), ("all-gather.8", r, r + 0.5),
                    ("top_k.41", r + 0.5, r + 0.75)]
            names += [LEAD, GATHER, MERGE]
        dev = f"/device:TPU:{c}"
        tr.ops[dev] = [(n, int(a * MS), int(b * MS)) for n, a, b in ops]
        tr.scopes[dev] = names
    tr.ops["/device:TPU:4"] = []
    tr.scopes["/device:TPU:4"] = []
    tr.spans = sorted(
        [(n, int(a * MS), int(b * MS), st) for n, a, b, st in (
            ("bench.window", 0, 10, {}),
            ("bench.step", 0.5, 4.5, {"queries": 128}),
            ("search.finish", 4, 4.25, {"shared_block_share": 0.98}),
            ("bench.step", 5, 9.5, {"queries": 128}),
            ("search.finish", 9, 9.25, {"shared_block_share": 0.96}),
            # after the last whole step: not read
            ("search.finish", 9.75, 9.9, {"shared_block_share": 0.5}))],
        key=lambda s: s[1])
    return tr


def test_gather_time_is_the_scope_per_step_and_chip():
    # 0.75 ms under dco.gather on each chip in each step
    assert read("engine.gather_ms.bulk", four_planes()) == pytest.approx(0.75)


def test_skew_is_latest_minus_earliest_arrival_at_the_gather():
    # step 1: 3 - 2 ms; step 2: 7 - 6 ms
    assert read("engine.gather_skew_ms.bulk", four_planes()) \
        == pytest.approx(1.0)


def test_skew_needs_two_chips():
    tr = four_planes()
    dev = "/device:TPU:0"
    one = stages.ProgramTrace(ops={dev: tr.ops[dev]},
                              scopes={dev: tr.scopes[dev]}, spans=tr.spans)
    assert read("engine.gather_skew_ms.bulk", one) is None
    assert read("engine.gather_ms.bulk", one) == pytest.approx(0.75)


def test_shared_block_share_is_the_finish_spans_mean_in_percent():
    assert read("facade.shared_block_share.bulk", four_planes()) \
        == pytest.approx(97.0)


def test_readers_find_nothing_in_a_program_without_the_scope_or_arg():
    """A trace of a program whose mesh merge has no scope and whose
    ``search.finish`` carries no share reads None on all three, as does a
    trace with no op names."""
    tr = four_planes()
    tr.scopes = {d: [n.replace("dco.gather/", "") for n in names]
                 for d, names in tr.scopes.items()}
    tr.spans = [(n, a, b, {} if n == "search.finish" else st)
                for n, a, b, st in tr.spans]
    assert [read(m, tr) for m in NEW] == [None, None, None]
    plain = tracing.Trace(ops=tr.ops, spans=tr.spans)
    assert [read(m, plain) for m in NEW] == [None, None, None]


def test_committed_benchmark_with_the_mesh_cell_has_no_problems():
    bench = manifest.load(ROOT)
    assert manifest.problems(ROOT, bench) == []
    cell = manifest.resolve(ROOT, CELL, bench)
    assert cell.chips == 4 and cell.config_name == "wiki768-4m"
    assert cell.traffic_name == "id-bulk"
    assert cell.config["data"]["n"] == 4_000_000
    assert cell.config["policy"]["row_block"] == 4096
    assert cell.config["reduced"] == ["n"]
    assert {m["name"] for m in cell.per_layer} == set(NEW)
    assert {m["name"] for m in cell.end_to_end} == {"qps", "setup_s"}
    for m in bench["per_layer"]:
        assert (CELL in m["workloads"]) == (m["name"] in NEW)
    one = json.loads((ROOT / "bench/configs/wiki768-1m.json").read_text())
    four = cell.config
    for key in ("method", "index", "k", "policy", "precision",
                "control_precision", "guarantees", "limits"):
        assert four[key] == one[key], key
    assert {k: v for k, v in four["data"].items() if k != "n"} \
        == {k: v for k, v in one["data"].items() if k != "n"}
