"""The reduction from a profiler trace to the per-layer metrics."""
from types import SimpleNamespace

import bench_testutil
import pytest

from bench import manifest, tracing, work

ROOT = bench_testutil.ROOT
MS = 1_000_000          # ns


def synthetic():
    """A 10 ms window: two steps of 4 ms with device work inside them, an
    idle wait between them, and a loop op that holds its body's ops."""
    tr = tracing.Trace()
    tr.ops["/device:TPU:0"] = [
        ("fusion.1", 0 * MS, 1 * MS),          # before the window: clipped
        ("while.1", 1 * MS, 3 * MS),           # holds the next two
        ("dco_scan", 1 * MS, 2 * MS),
        ("top_k", int(2.5 * MS), 3 * MS),
        ("dco_scan", 6 * MS, 7 * MS),
        ("fusion.2", 7 * MS, 8 * MS),
    ]
    tr.spans = [
        ("bench.window", 1 * MS, 11 * MS, {}),
        ("bench.step", 1 * MS, 5 * MS, {"queries": 16}),
        ("bench.idle_wait", 5 * MS, 6 * MS, {}),
        ("bench.step", 6 * MS, 10 * MS, {"queries": 8}),
        ("bench.submit", 10 * MS, 11 * MS, {}),
    ]
    return tr


def test_busy_is_the_union_inside_the_window():
    tr = synthetic()
    # [1, 3] and [6, 8] ms: nested ops count once, the early op not at all
    assert tracing.device_busy_s(tr) == pytest.approx(4e-3)
    assert tracing.window_s(tr) == pytest.approx(10e-3)


def test_device_time_per_step_span():
    assert tracing.step_busy_ms(synthetic()) == pytest.approx([2.0, 2.0])


def test_kernel_time_by_event_name():
    ops = tracing.matching(synthetic().ops["/device:TPU:0"], ("dco_scan",))
    assert sum(b - a for _, a, b in ops) == 2 * MS


def test_top_ops_and_idle_gaps():
    tr = synthetic()
    top = dict(tracing.top_ops(tr))
    assert top["dco_scan"] == pytest.approx(2e-3)
    assert top["while.1"] == pytest.approx(0.5e-3)   # its own time only
    assert "fusion.1" not in top
    gaps = tracing.idle_gaps(tr)
    # idle: [3, 6] split by spans (mid 4.5 ms in step 1), [8, 11] (mid 9.5
    # ms in step 2)
    assert gaps == [["bench.step", pytest.approx(3e-3)],
                    ["bench.step", pytest.approx(3e-3)]]


def test_a_window_must_be_marked_once():
    tr = synthetic()
    tr.spans = tr.spans[1:]
    with pytest.raises(ValueError):
        tr.window()


def _ctx(tr, n=1_000_000):
    cfg = {"data": {"n": n}, "policy": {"d1": 128, "query_chunk": 16}}
    return SimpleNamespace(trace=tr, config=cfg, traffic={},
                           window=None, peaks=work.peaks("TPU v5 lite"))


def test_per_layer_readers_on_the_synthetic_trace():
    ctx = _ctx(synthetic())
    read = lambda name: manifest.reader(ROOT, name)(ctx)   # noqa: E731
    assert read("device.idle.bulk") == pytest.approx(60.0)
    assert read("engine.device_ms.bulk") == pytest.approx(2.0)
    # two steps: 16 queries (1 chunk) and 8 queries (1 chunk), 1 ms kernel
    # each; bytes bound: 1M x 128 x 4 B per chunk at 819 GB/s
    least = 2 * 4.0 * 1_000_000 * 128 / 819e9
    assert read("dco_scan_roofline") == pytest.approx(100 * least / 2e-3)


def test_roofline_is_silent_where_the_kernel_did_not_run():
    tr = synthetic()
    tr.ops["/device:TPU:0"] = [e for e in tr.ops["/device:TPU:0"]
                               if e[0] != "dco_scan"]
    assert manifest.reader(ROOT, "dco_scan_roofline")(_ctx(tr)) is None


def recorded():
    import json
    d = json.loads((ROOT / "tests/bench/data/tpu_trace_excerpt.json")
                   .read_text())
    return tracing.Trace(ops={d["device"]: [tuple(e) for e in d["ops"]]},
                         spans=[tuple(s) for s in d["spans"]])


def test_recorded_trace_nesting_and_busy():
    tr = recorded()
    w0, w1 = tr.window()
    ops = tr.ops["/device:TPU:0"]
    # the scan's while op holds its body's ops on the same line: own times
    # add up to the union, so nothing is counted twice
    own = tracing.self_times(ops, w0, w1)
    assert sum(own.values()) == tracing.busy_ns(ops, w0, w1)
    assert tracing.device_busy_s(tr) == pytest.approx(194019e-9)
    assert {name for name, _ in tracing.top_ops(tr)} >= {"sort.8",
                                                         "dco_scan.4"}


def test_recorded_trace_gap_between_steps():
    tr = recorded()
    (name, sec), *_ = tracing.idle_gaps(tr)
    # the device is idle for ~3 ms while the host finishes one step and
    # prepares the next
    assert name == "bench.step" and sec == pytest.approx(2.991495e-3)
    kern = tracing.matching(tr.ops["/device:TPU:0"], ("dco_scan",))
    assert [e[0] for e in kern] == ["dco_scan.4", "dco_scan.4"]


def test_op_names_are_cut_from_the_hlo_text():
    assert tracing.op_name("%fusion.20 = f32[2064]{0} fusion(f32[16]{0} %x)"
                           ) == "fusion.20"
    assert tracing.op_name("custom name") == "custom name"
