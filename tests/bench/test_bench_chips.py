"""The trace reductions over the chips a cell used: on four device planes
each reads the per-chip mean (a gap is a stretch in which no chip ran
anything), and on the recorded one-chip excerpts each reads, to the bit,
what the one-chip reductions read there before they were defined per chip
(``tests/bench/data/one_chip_reductions.json``)."""
import json
from types import SimpleNamespace

import bench_testutil
import pytest
from test_bench_trace import recorded, synthetic

from bench import manifest, stages, tracing, work

ROOT = bench_testutil.ROOT
DATA = ROOT / "tests/bench/data"
MS = 1_000_000          # ns
READERS = ("device.idle.bulk", "engine.device_ms.bulk", "dco_scan_roofline",
           "engine.lead_ms.bulk", "engine.compact_ms.bulk",
           "engine.tail_ms.bulk", "engine.merge_ms.bulk",
           "backend.prep_ms.bulk", "backend.fetch_idle_ms.bulk")
LEAD = "jit(_stream_topk_padded)/dco.lead/pallas_call"
TAIL = "jit(_stream_topk_padded)/dco.tail/gather"


def _ctx(tr, cfg):
    return SimpleNamespace(trace=tr, config=cfg, traffic={}, window=None,
                           peaks=work.peaks("TPU v5 lite"))


def readings(tr, cfg) -> dict:
    """Every reduction and existing reader of ``tr``, as JSON holds them."""
    ctx = _ctx(tr, cfg)
    names = sorted({s[0] for s in tr.spans if s[0].startswith("search.")})
    return json.loads(json.dumps({
        "device_busy_s": tracing.device_busy_s(tr),
        "window_s": tracing.window_s(tr),
        "step_busy_ms": tracing.step_busy_ms(tr),
        "top_ops": tracing.top_ops(tr),
        "idle_gaps": tracing.idle_gaps(tr, n=10_000),
        "scope_own_ms": {str(k): v
                         for k, v in stages.scope_own_ms(tr).items()},
        "idle_in": {n: stages.idle_in(tr, n) for n in names},
        "idle_coverage": list(stages.idle_coverage(tr)) if names else None,
        "readers": {m: manifest.reader(ROOT, m)(ctx) for m in READERS},
    }))


def _config(name):
    return json.loads((ROOT / f"bench/configs/{name}.json").read_text())


def _excerpt(which):
    if which == "tpu_trace_excerpt":
        return recorded(), _config("wiki768-1m")
    d = json.loads((DATA / "tpu_trace_program_excerpt.json").read_text())
    cfg = "wiki768-1m" if which.startswith("wiki") else "laion512-2m-adaptive"
    return stages.from_excerpt(d[which]), _config(cfg)


@pytest.mark.parametrize("which", ["tpu_trace_excerpt", "wiki768-1m.id-bulk",
                                   "laion512-2m.ood-bulk"])
def test_one_chip_excerpts_read_as_before(which):
    want = json.loads((DATA / "one_chip_reductions.json").read_text())[which]
    got = readings(*_excerpt(which))
    assert got == want            # exact: no tolerance


def four_chips():
    """A 10 ms window of two steps on four chips.  Chip 1 runs alone in
    [3, 4] ms and chip 3 idles through the second step while the others
    run; a fifth plane ran nothing and is not one of the cell's chips."""
    plan = {
        0: [("dco_scan.4", 1, 2), ("fusion.1", 2, 3), ("dco_scan.4", 6, 7)],
        1: [("dco_scan.4", 1, 2), ("fusion.1", 2, 4), ("dco_scan.4", 6, 7)],
        2: [("dco_scan.4", 1, 2.5), ("dco_scan.4", 6, 7)],
        3: [("dco_scan.4", 1, 2)],
    }
    tr = stages.ProgramTrace()
    for c, ops in plan.items():
        tr.ops[f"/device:TPU:{c}"] = [(n, int(a * MS), int(b * MS))
                                      for n, a, b in ops]
        tr.scopes[f"/device:TPU:{c}"] = [LEAD if n.startswith("dco") else TAIL
                                         for n, _, _ in ops]
    tr.ops["/device:TPU:4"] = []
    tr.scopes["/device:TPU:4"] = []
    tr.spans = sorted(
        [(n, int(a * MS), int(b * MS), st) for n, a, b, st in (
            ("bench.window", 0, 10, {}),
            ("bench.step", 0.5, 4.5, {"queries": 16}),
            ("search.fetch", 1, 4.5, {}),
            ("bench.submit", 4.5, 5.5, {}),
            ("bench.step", 5.5, 9.5, {"queries": 16}),
            ("search.fetch", 6, 9.5, {}))],
        key=lambda s: s[1])
    return tr


def test_busy_and_step_time_are_per_chip_means():
    tr = four_chips()
    assert len(tracing.used_chips(tr)) == 4
    # busy 3, 4, 2.5 and 1 ms of the window
    assert tracing.device_busy_s(tr) == pytest.approx(2.625e-3)
    # step 1: 2, 3, 1.5, 1 ms; step 2: 1, 1, 1 and chip 3's 0 ms
    assert tracing.step_busy_ms(tr) == pytest.approx([1.875, 0.75])


def test_top_ops_are_summed_over_chips_and_divided_by_their_count():
    top = dict(tracing.top_ops(four_chips()))
    assert top == pytest.approx({"dco_scan.4": 7.5e-3 / 4,
                                 "fusion.1": 3e-3 / 4})


def test_a_gap_is_where_no_chip_ran():
    # busy on some chip: [1, 4] and [6, 7] ms; gaps [0, 1] (mid in step 1),
    # [4, 6] (mid in bench.submit), [7, 10] (mid in step 2's fetch)
    assert tracing.idle_gaps(four_chips()) == [
        ["search.fetch", pytest.approx(3e-3)],
        ["bench.submit", pytest.approx(2e-3)],
        ["bench.step", pytest.approx(1e-3)]]


def test_stage_reductions_are_per_chip_means():
    tr = four_chips()
    # lead: 4.5 + 3 ms over four chips and two steps; tail: 1 + 2 ms
    assert stages.scope_own_ms(tr) == pytest.approx(
        {"dco.lead": 7.5 / 8, "dco.tail": 3 / 8})
    # idle inside search.fetch, steps 1 + 2: chip 0 1.5 + 2.5, chip 1
    # 0.5 + 2.5, chip 2 2 + 2.5, chip 3 2.5 + 3.5 ms
    assert stages.idle_in(tr, "search.fetch") == pytest.approx(17.5 / 8)


def test_readers_on_four_chips():
    tr = four_chips()
    cfg = {"data": {"n": 4_000_000},
           "policy": {"d1": 128, "query_chunk": 16}}
    read = lambda name: manifest.reader(ROOT, name)(_ctx(tr, cfg))  # noqa
    assert read("device.idle.bulk") == pytest.approx(100 * (1 - 0.2625))
    assert read("engine.device_ms.bulk") == pytest.approx(1.3125)
    assert read("engine.lead_ms.bulk") == pytest.approx(7.5 / 8)
    assert read("backend.fetch_idle_ms.bulk") == pytest.approx(17.5 / 8)
    # seven chip-steps ran the kernel, each over its own 1M rows (one
    # 16-query chunk: bytes bound), for 7.5 ms of kernel time in all
    least = 4.0 * 1_000_000 * 128 / 819e9
    assert read("dco_scan_roofline") == pytest.approx(100 * 7 * least / 7.5e-3)
    # the same kernel time on one chip would have screened all 4M rows
    one = tracing.Trace(ops={"/device:TPU:0": tr.ops["/device:TPU:0"]},
                        spans=tr.spans)
    assert manifest.reader(ROOT, "dco_scan_roofline")(_ctx(one, cfg)) \
        == pytest.approx(100 * 2 * 4 * least / 2e-3)


@pytest.mark.parametrize("fixture", [synthetic, recorded])
def test_four_chips_alike_read_as_one(fixture):
    """The same operations on four chips read exactly as on one."""
    one = fixture()
    (dev, ops), = one.ops.items()
    four = tracing.Trace(ops={f"{dev}:{c}": list(ops) for c in range(4)},
                         spans=one.spans)
    for fn in (tracing.device_busy_s, tracing.step_busy_ms, tracing.top_ops,
               tracing.idle_gaps):
        assert fn(four) == fn(one)


def test_shard_rows_divides_the_corpus():
    assert work.shard_rows(4_000_000, 1) == 4_000_000
    assert work.shard_rows(4_000_000, 4) == 1_000_000


#: two TPU planes whose event metadata ids overlap but name different ops
#: and scopes, a line beside "XLA Ops" and an op event with stats of its
#: own; the host plane holds a span
XSPACE_TWO_CHIPS = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 0 duration_ps: 9000000 } }
  lines {
    id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000
             stats { metadata_id: 12 int64_value: 7 } }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%dco_scan.4 = f32[8]{0} x()"
    stats { metadata_id: 10 str_value: "jit(f)/dco.lead/pallas_call:" } } }
  event_metadata { key: 2 value { id: 2 name: "%sort.2 = f32[8]{0} sort()"
    stats { metadata_id: 10 ref_value: 11 } } }
  stat_metadata { key: 10 value { id: 10 name: "tf_op" } }
  stat_metadata { key: 11 value { id: 11 name: "jit(f)/dco.merge/top_k:" } }
  stat_metadata { key: 12 value { id: 12 name: "run_id" } }
}
planes {
  id: 2 name: "/device:TPU:1"
  lines {
    id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 500000 duration_ps: 1500000 }
    events { metadata_id: 1 offset_ps: 2000000 duration_ps: 500000 }
    events { metadata_id: 3 offset_ps: 4000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%gather.9 = f32[8]{0} g()"
    stats { metadata_id: 5 str_value: "jit(f)/dco.tail/gather:" } } }
  event_metadata { key: 2 value { id: 2 name: "%dco_scan.4 = f32[8]{0} x()"
    stats { metadata_id: 5 str_value: "jit(f)/dco.lead/pallas_call:" } } }
  event_metadata { key: 3 value { id: 3 name: "%all-gather.1 = f32[8]{0} a()" } }
  stat_metadata { key: 5 value { id: 5 name: "tf_op" } }
}
planes {
  id: 3 name: "/host:CPU"
  lines {
    id: 1 name: "python3" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 9000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
}
"""


def test_load_reads_each_chip_plane_with_its_own_metadata(tmp_path):
    from jax.profiler import ProfileData
    (tmp_path / "t.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(XSPACE_TWO_CHIPS))
    tr = stages.load(tmp_path)
    assert tr.ops == {
        "/device:TPU:0": [("dco_scan.4", 1000, 3000), ("sort.2", 4000, 5000)],
        "/device:TPU:1": [("dco_scan.4", 1500, 3000),
                          ("gather.9", 3000, 3500),
                          ("all-gather.1", 5000, 6000)]}
    assert tr.scopes == {
        "/device:TPU:0": ["jit(f)/dco.lead/pallas_call",
                          "jit(f)/dco.merge/top_k"],
        "/device:TPU:1": ["jit(f)/dco.lead/pallas_call",
                          "jit(f)/dco.tail/gather", ""]}
    assert tr.spans == [("bench.window", 1000, 10000, {})]
    assert list(tracing.used_chips(tr)) == ["/device:TPU:0", "/device:TPU:1"]
    # 3,000 ns busy on each chip
    assert tracing.device_busy_s(tr) == pytest.approx(3e-6)


def test_per_span_finds_what_a_walk_of_the_whole_trace_finds():
    """Each span gets exactly the events that overlap it, a ``while`` that
    starts before the span among them, and ``self_times`` of that slice
    equals ``self_times`` of the whole trace wherever the time is not 0."""
    ev = [("while.1", 0, 50), ("a", 1, 5), ("b", 8, 12), ("a", 20, 30),
          ("c", 22, 24), ("b", 30, 40), ("d", 60, 70), ("a", 70, 71)]
    spans = [("bench.step", 10, 25, {}), ("bench.step", 30, 70, {}),
             ("bench.step", 80, 90, {})]
    got = tracing.per_span(list(reversed(ev)), spans)
    for (_, lo, hi, _), sl in zip(spans, got):
        assert sl == [e for e in ev if e[1] < hi and e[2] > lo]
        whole = {k: v for k, v in tracing.self_times(ev, lo, hi).items() if v}
        assert {k: v for k, v in tracing.self_times(sl, lo, hi).items()
                if v} == whole
    assert [len(sl) for sl in got] == [4, 3, 0]
