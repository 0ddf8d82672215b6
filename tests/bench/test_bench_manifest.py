"""BENCHMARK.json and the files it names."""
import json
import re

import bench_testutil
import pytest

from bench import manifest, work

ROOT = bench_testutil.ROOT


@pytest.fixture(scope="module")
def bench():
    return manifest.load(ROOT)


def test_committed_benchmark_has_no_problems(bench):
    assert manifest.problems(ROOT, bench) == []


def test_every_cell_resolves_by_name(bench):
    for w in bench["workloads"]:
        cell = manifest.resolve(ROOT, w["name"], bench)
        assert cell.config["data"]["n"] > 0
        assert cell.traffic["loop"] in ("open", "closed")
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
        assert cell.per_layer
        for m in cell.per_layer:
            assert callable(manifest.reader(ROOT, m["name"]))


def test_contract_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    assert bench["command"][1].startswith(tuple(bench["paths"]))
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert re.fullmatch(r"[^\n\t]{1,200}", m["layer"])
    for c in bench["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert sorted(c["reduced"]) == sorted(cfg["reduced"])


@pytest.mark.parametrize("bad, what", [
    ({"name": "has space"}, "not a valid name"),
    ({"name": "a/b"}, "not a valid name"),
    ({"name": "x" * 65}, "not a valid name"),
    ({"unit": "queries per second"}, "unit"),
    ({"unit": "µs"}, "unit"),
    ({"better": "smaller"}, "better"),
])
def test_bad_names_and_units_are_found(bench, bad, what):
    b = json.loads(json.dumps(bench))
    b["end_to_end"][0].update(bad)
    assert any(what in p for p in manifest.problems(ROOT, b))


def test_four_chip_share_is_held(bench):
    b = json.loads(json.dumps(bench))
    for w in b["workloads"][:2]:
        w["chips"] = 4
    assert any("four-chip" in p for p in manifest.problems(ROOT, b))


def test_missing_files_are_found(bench, tmp_path):
    b = json.loads(json.dumps(bench))
    b["workloads"][0]["traffic"] = "no-such-mix"
    b["per_layer"].append(dict(b["per_layer"][0], name="no.such.metric"))
    found = manifest.problems(ROOT, b)
    assert any("no traffic file" in p for p in found)
    assert any("no reader file" in p for p in found)


def test_unknown_device_kind_raises():
    assert work.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")


def test_screen_work_is_counted_from_real_queries():
    flop, nbytes = work.screen_work(1_000_000, 128, 128, 16)
    assert flop == 2.0 * 1_000_000 * 128 * 128
    assert nbytes == 4.0 * 1_000_000 * 128 * 8       # one read per chunk
    # a ragged batch is charged its own chunks, not a padded tile
    assert work.screen_work(1000, 128, 17, 16)[1] == 4.0 * 1000 * 128 * 2
    t, bound = work.least_time_s(flop, nbytes, work.peaks("TPU v5 lite"))
    assert bound == "bytes" and t == pytest.approx(nbytes / 819e9)


@pytest.mark.parametrize("chips, n, what", [
    (4, 1_000_002, "n 1000002 is not a multiple of its 4 chips"),
    (4, 1_000_000, None),
    (1, 1_000_003, None),
    (2, 1_000_000, "chips 2"),
    (3, 999_999, "chips 3"),
], ids=["ragged-rows", "four-chips", "one-chip", "two-chips", "three-chips"])
def test_shards_are_checked(tmp_path, chips, n, what):
    """A cell's corpus rows divide evenly over its chips, one shard a
    chip, and a cell has one chip or four."""
    root = bench_testutil.tiny_root(tmp_path)
    path = root / "bench/configs/wiki768-1m.json"
    cfg = json.loads(path.read_text())
    cfg["data"]["n"] = n
    path.write_text(json.dumps(cfg))
    b = manifest.load(root)
    b["workloads"][0]["chips"] = chips
    found = manifest.problems(root, b)
    if what is None:
        assert found == []
    else:
        assert len(found) == 1 and what in found[0], found
