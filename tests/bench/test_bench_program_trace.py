"""The program's spans and device scopes in a profiler trace
(``bench.stages``) and the six per-layer metrics that read them."""
import json
import tempfile
from types import SimpleNamespace

import bench_testutil
import numpy as np
import pytest
from test_bench_trace import recorded, synthetic

from bench import manifest, stages, tracing, work

ROOT = bench_testutil.ROOT
MS = 1_000_000          # ns
SCAN = "jit(_stream_topk_padded)/while/body/closed_call/dco.scan/while"
BODY = SCAN + "/body/closed_call/"
NEW = ("engine.lead_ms.bulk", "engine.compact_ms.bulk",
       "engine.tail_ms.bulk", "engine.merge_ms.bulk",
       "backend.prep_ms.bulk", "backend.fetch_idle_ms.bulk")
OLD = ("facade.step_ms.bulk", "engine.device_ms.bulk", "dco_scan_roofline",
       "device.idle.bulk")


def _ms(*spans):
    return [(n, int(a * MS), int(b * MS), st) for n, a, b, st in spans]


def _step(step, t, prep, fetch, finish):
    """The program's spans of one step that starts at ``t`` ms, as
    ``SearchService.step`` records them; ``prep``, ``fetch`` and
    ``finish`` are where those spans end."""
    return _ms(
        ("search.step", t + 0.1, t + 4.9,
         {"step": step, "queries": 128, "slots": 128, "first_rid": 128 * step}),
        ("search.batch", t + 0.1, t + 0.2, {"expired": 0}),
        ("search.prep", t + 0.2, prep, {}),
        ("search.dispatch", prep, prep + 0.1, {"chunks": 8}),
        ("search.fetch", prep + 0.1, fetch, {}),
        ("search.finish", fetch, finish, {}),
        ("search.tickets", finish, t + 4.8, {"served": 128}))


def program_synthetic():
    """Two 5 ms steps, each a scan whose ``while`` op holds one op of each
    stage, inside a 10 ms window."""
    dev = "/device:TPU:0"
    ops, scopes = [], []
    for t, (lead, compact, tail, merge) in ((0.5, (1.0, 0.5, 1.0, 0.5)),
                                            (5.5, (0.5, 1.0, 0.5, 0.5))):
        ops.append(("while.1", t, t + 3.0))
        scopes.append(SCAN)
        for name, scope, ms in (("dco_scan.4", "dco.lead/pallas_call", lead),
                                ("sort.8", "dco.compact/top_k", compact),
                                ("fusion.20", "dco.tail/gather", tail),
                                ("sort.9", "dco.merge/top_k", merge)):
            ops.append((name, t, t + ms))
            scopes.append(BODY + scope)
            t += ms
    ops.append(("copy.1", 3.5, 3.6))           # outside every scope
    scopes.append("jit(_stream_topk_padded)/copy")
    tr = stages.ProgramTrace(
        ops={dev: [(n, int(a * MS), int(b * MS)) for n, a, b in ops]},
        scopes={dev: scopes})
    tr.spans = sorted(
        _ms(("bench.window", 0, 10, {}), ("bench.step", 0, 5, {}),
            ("bench.step", 5, 10, {}))
        + _step(0, 0, prep=0.5, fetch=4.0, finish=4.2)
        + _step(1, 5, prep=5.4, fetch=9.0, finish=9.3),
        key=lambda s: s[1])
    return tr


def _ctx(tr):
    cfg = {"data": {"n": 1_000_000}, "policy": {"d1": 128, "query_chunk": 16}}
    window = SimpleNamespace(steps=[SimpleNamespace(service_s=4e-3)])
    return SimpleNamespace(trace=tr, config=cfg, traffic={}, window=window,
                           peaks=work.peaks("TPU v5 lite"))


def _read(name, tr):
    return manifest.reader(ROOT, name)(_ctx(tr))


def test_scope_own_times_per_step():
    own = stages.scope_own_ms(program_synthetic())
    # step 1: lead 1.0, compact 0.5, tail 1.0, merge 0.5, the while op's own
    # time 0; step 2: 0.5, 1.0, 0.5, 0.5 and 0.5; copy.1 0.1 in step 1
    assert own == pytest.approx({"dco.lead": 0.75, "dco.compact": 0.75,
                                 "dco.tail": 0.75, "dco.merge": 0.5,
                                 "dco.scan": 0.25, None: 0.05})
    assert sum(own.values()) == pytest.approx(
        _read("engine.device_ms.bulk", program_synthetic()))


def test_span_time_and_idle_inside_spans():
    tr = program_synthetic()
    assert stages.span_ms(tr, "search.prep") == pytest.approx(0.25)
    # fetch [0.6, 4.0] holds busy [0.6, 3.6]; [5.5, 9.0] holds [5.5, 8.5]
    assert stages.idle_in(tr, "search.fetch") == pytest.approx(0.45)
    assert stages.span_ms(tr, "search.group_sync") is None
    # idle in the search.step spans: [0.1, 0.5] + [3.6, 4.9] and
    # [5.1, 5.5] + [8.5, 9.9]; the children end 0.1 ms before their parent
    idle, covered = stages.idle_coverage(tr)
    assert (idle / MS, covered / MS) == pytest.approx((3.5, 3.3))


def test_the_six_readers_on_the_synthetic_trace():
    tr = program_synthetic()
    got = {name: _read(name, tr) for name in NEW}
    assert got == pytest.approx({
        "engine.lead_ms.bulk": 0.75, "engine.compact_ms.bulk": 0.75,
        "engine.tail_ms.bulk": 0.75, "engine.merge_ms.bulk": 0.5,
        "backend.prep_ms.bulk": 0.25, "backend.fetch_idle_ms.bulk": 0.45})


def test_the_six_readers_are_silent_on_a_trace_without_program_spans():
    for tr in (synthetic(), recorded()):          # tracing.Trace, as run.py
        assert all(_read(name, tr) is None for name in NEW)


def test_gaps_are_named_by_the_program_spans():
    gaps = tracing.idle_gaps(program_synthetic())
    # idle [0, 0.5] (mid in search.prep), [3.6, 5.5] (mid 4.55 in
    # search.tickets) and [8.5, 10] (mid 9.25 in search.finish)
    assert gaps == [["search.tickets", pytest.approx(1.9e-3)],
                    ["search.finish", pytest.approx(1.5e-3)],
                    ["search.prep", pytest.approx(0.5e-3)]]


@pytest.mark.parametrize("fixture", [synthetic, recorded])
def test_existing_reductions_unchanged_beside_program_spans(fixture):
    """The existing fixtures read as today when the program's spans and an
    empty scope list sit beside them; a gap inside ``search.fetch`` is named
    ``search.fetch``."""
    plain = fixture()
    w0, w1 = plain.window()
    fetch = ("search.fetch", w0 + 2 * MS - MS // 5, w0 + 3 * MS + 6 * MS // 10,
             {})
    tr = stages.ProgramTrace(ops=plain.ops, spans=sorted(
        plain.spans + [fetch], key=lambda s: s[1]),
        scopes={d: [""] * len(ev) for d, ev in plain.ops.items()})
    for name in OLD:
        assert _read(name, tr) == _read(name, plain)
    assert tracing.top_ops(tr) == tracing.top_ops(plain)
    assert [g[1] for g in tracing.idle_gaps(tr)] == \
        [g[1] for g in tracing.idle_gaps(plain)]
    if fixture is synthetic:   # idle [3, 6] ms of the window: mid in fetch
        assert tracing.idle_gaps(tr)[0] == ["search.fetch",
                                            pytest.approx(3e-3)]


#: a TPU-shaped trace: op events whose metadata carries the op_name as
#: XProf's ``tf_op`` stat, once as a string and once by reference
XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines {
    id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1
    name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
    stats { metadata_id: 10 str_value: "jit(f)/dco.lead/dot_general:" } } }
  event_metadata { key: 2 value { id: 2 name: "%sort.2 = f32[8]{0} sort()"
    stats { metadata_id: 10 ref_value: 11 } } }
  event_metadata { key: 3 value { id: 3 name: "%copy.3 = f32[8]{0} copy()" } }
  stat_metadata { key: 10 value { id: 10 name: "tf_op" } }
  stat_metadata { key: 11 value { id: 11 name: "jit(f)/dco.merge/top_k:" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines {
    id: 2 name: "python3" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 9000000
             stats { metadata_id: 20 int64_value: 3 } }
    events { metadata_id: 2 offset_ps: 100000 duration_ps: 1000 }
  }
  event_metadata { key: 1 value { id: 1 name: "search.step" } }
  event_metadata { key: 2 value { id: 2 name: "other" } }
  stat_metadata { key: 20 value { id: 20 name: "queries" } }
}
"""


def test_load_reads_op_names_and_program_spans(tmp_path):
    from jax.profiler import ProfileData
    (tmp_path / "t.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(XSPACE))
    tr = stages.load(tmp_path)
    assert tr.ops == {"/device:TPU:0": [("fusion.1", 1000, 3000),
                                        ("sort.2", 4000, 5000),
                                        ("copy.3", 6000, 7000)]}
    assert tr.scopes == {"/device:TPU:0": ["jit(f)/dco.lead/dot_general",
                                           "jit(f)/dco.merge/top_k", ""]}
    assert tr.spans == [("search.step", 1000, 10000, {"queries": 3})]
    assert [stages.scope_of(n) for n in tr.scopes["/device:TPU:0"]] == \
        ["dco.lead", "dco.merge", None]


@pytest.fixture(scope="module")
def service_trace():
    """One ``SearchService.step`` on a tiny adaptive jax index, traced on
    the CPU and read by ``stages.load``."""
    import jax

    from repro.api import SchedulePolicy, open_index
    rng = np.random.default_rng(0)
    X = rng.standard_normal((2048, 32)).astype(np.float32)
    svc = open_index(X, method="PDScanning+", backend="jax", serving=True,
                     schedule=SchedulePolicy(d1=8, row_block=512,
                                             query_chunk=8, block_capacity=64,
                                             adaptive=True),
                     serving_params={"slots": 16, "k": 5})
    for q in X[:16]:
        svc.submit(q)
    svc.step()                                  # compiles
    for q in X[16:28]:
        svc.submit(q)
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            svc.step()
        finally:
            jax.profiler.stop_trace()
        return stages.load(d)


def test_a_served_step_records_its_span_tree(service_trace):
    tr = service_trace
    spans = [s for s in tr.spans if s[0].startswith("search.")]
    assert [s[0] for s in spans] == [
        "search.step", "search.batch", "search.prep", "search.prep",
        "search.dispatch", "search.seed_sync", "search.fetch",
        "search.finish", "search.tickets"]
    step, dispatch, sync = spans[0], spans[4], spans[5]
    assert all(step[1] <= s[1] and s[2] <= step[2] for s in spans[1:])
    assert dispatch[1] <= sync[1] and sync[2] <= dispatch[2]
    children = [s for s in spans[1:] if s is not sync]
    assert all(a[2] <= b[1] for a, b in zip(children, children[1:]))
    assert step[3] == {"step": 1, "queries": 12, "slots": 16,
                       "first_rid": 16}
    assert spans[1][3] == {"expired": 0}
    assert set(dispatch[3]) == {"chunks", "full_chunks"}
    assert dispatch[3]["chunks"] == 2 and 0 <= dispatch[3]["full_chunks"] <= 2
    assert spans[-1][3] == {"served": 12}


def test_recorded_chip_excerpt():
    """One step boundary of each cell, recorded on the chip: the six
    readers give the excerpt's numbers (spans are cut at the slice's
    edges, so each cut step counts as a step)."""
    data = json.loads((ROOT / "tests/bench/data/tpu_trace_program_excerpt"
                       ".json").read_text())
    assert set(data) == {"wiki768-1m.id-bulk", "laion512-2m.ood-bulk"}
    # the forced full-scan body of the cross-modal cell compacts nothing
    silent = {"wiki768-1m.id-bulk": set(),
              "laion512-2m.ood-bulk": {"engine.compact_ms.bulk"}}
    for cell, d in data.items():
        tr = stages.from_excerpt(d)
        got = {name: _read(name, tr) for name in NEW}
        assert {n for n, v in got.items() if v is None} == silent[cell]
        assert got == pytest.approx(d["numbers"]), cell
        # every op's time is some scope's or none's: the own times add up
        # to the device's busy time
        assert sum(stages.scope_own_ms(tr).values()) == pytest.approx(
            _read("engine.device_ms.bulk", tr))
