"""``BENCHMARK.json`` and the files it names, found by name.

A cell (an entry of ``workloads``) names a configuration and a traffic mix.
The configuration's file is the one its ``configs`` entry gives; the traffic
mix is ``bench/traffic/<traffic>.json``; a per-layer metric is read by
``bench/metrics/<name>.py``.  Nothing here knows any cell, configuration,
mix or metric by name.
"""
from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass
from pathlib import Path

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TRAFFIC_DIR = Path("bench") / "traffic"
METRICS_DIR = Path("bench") / "metrics"


@dataclass
class Cell:
    """One workload, with its configuration, traffic mix and metrics."""

    name: str
    chips: int
    config_name: str
    config: dict          # the configuration's file
    traffic_name: str
    traffic: dict         # the traffic mix's file
    end_to_end: list      # BENCHMARK.json entries this cell reports
    per_layer: list


def load(root) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell_metrics(bench: dict, cell: str):
    """(end_to_end, per_layer) entries that ``cell`` reports.  A per-layer
    metric with no ``workloads`` key applies wherever the end-to-end metric
    it moves is reported."""
    e2e = [m for m in bench["end_to_end"] if _applies(m, cell)]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if m["moves"] in names and _applies(m, cell)]
    return e2e, layer


def resolve(root, name: str, bench: dict | None = None) -> Cell:
    """The cell ``name`` of the benchmark under ``root``."""
    root = Path(root)
    bench = load(root) if bench is None else bench
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e, layer = cell_metrics(bench, name)
    return Cell(
        name=name, chips=int(w["chips"]), config_name=cfg["name"],
        config=json.loads((root / cfg["file"]).read_text()),
        traffic_name=w["traffic"],
        traffic=json.loads((root / TRAFFIC_DIR / f"{w['traffic']}.json")
                           .read_text()),
        end_to_end=e2e, per_layer=layer)


def reader(root, metric: str):
    """The ``read(ctx)`` function of a per-layer metric's file."""
    path = Path(root) / METRICS_DIR / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + re.sub(r"\W", "_", metric), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def problems(root, bench: dict | None = None) -> list:
    """What in ``BENCHMARK.json`` breaks the harness's rules: names and
    units out of their characters, duplicate names, files that cannot be
    found by name, cells over the four-chip share, a cell whose corpus rows
    do not divide evenly over its chips, metrics that move an end-to-end
    metric the benchmark does not have or that name no cell."""
    root = Path(root)
    bench = load(root) if bench is None else bench
    out = []
    named = [("config", c["name"]) for c in bench["configs"]] + \
        [("workload", w["name"]) for w in bench["workloads"]] + \
        [("metric", m["name"]) for m in bench["end_to_end"] + bench["per_layer"]]
    for w in bench["workloads"]:
        named += [("traffic", w["traffic"]), ("config ref", w["config"])]
    for c in bench["configs"]:
        named += [("reduced", key) for key in c["reduced"]]
    for kind, name in named:
        if not NAME_RE.match(name):
            out.append(f"{kind} name {name!r} is not a valid name")
    for group in ("configs", "workloads"):
        names = [e["name"] for e in bench[group]]
        if len(set(names)) != len(names):
            out.append(f"duplicate names in {group}")
    metrics = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    if len(set(metrics)) != len(metrics):
        out.append("duplicate metric names")
    for m in bench["end_to_end"] + bench["per_layer"]:
        if not UNIT_RE.match(m["unit"]):
            out.append(f"metric {m['name']}: unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            out.append(f"metric {m['name']}: better {m['better']!r}")
    configs = {c["name"]: c for c in bench["configs"]}
    cells = {w["name"] for w in bench["workloads"]}
    rows = {}
    for c in bench["configs"]:
        if not (root / c["file"]).is_file():
            out.append(f"config {c['name']}: no file {c['file']}")
            continue
        rows[c["name"]] = int(json.loads((root / c["file"]).read_text())
                              ["data"]["n"])
    for w in bench["workloads"]:
        if w["config"] not in configs:
            out.append(f"workload {w['name']}: no config {w['config']!r}")
        elif w["config"] in rows and rows[w["config"]] % w["chips"]:
            out.append(f"workload {w['name']}: n {rows[w['config']]} is not "
                       f"a multiple of its {w['chips']} chips")
        if not (root / TRAFFIC_DIR / f"{w['traffic']}.json").is_file():
            out.append(f"workload {w['name']}: no traffic file for "
                       f"{w['traffic']!r}")
        if w["chips"] not in (1, 4):
            out.append(f"workload {w['name']}: chips {w['chips']}")
        e2e, layer = cell_metrics(bench, w["name"])
        if "setup_s" not in {m["name"] for m in e2e} or len(e2e) < 2 \
                or not layer:
            out.append(f"workload {w['name']}: needs setup_s, another "
                       "end-to-end metric and a per-layer metric")
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    if len(set(pairs)) != len(pairs):
        out.append("a (config, traffic) pair appears twice")
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    if four > max(1, len(bench["workloads"]) // 2):
        out.append(f"{four} four-chip cells of {len(bench['workloads'])}")
    e2e_names = {m["name"] for m in bench["end_to_end"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        for cell in m.get("workloads", []):
            if cell not in cells:
                out.append(f"metric {m['name']}: no workload {cell!r}")
    for m in bench["per_layer"]:
        if m["moves"] not in e2e_names:
            out.append(f"metric {m['name']}: moves unknown {m['moves']!r}")
        if not (root / METRICS_DIR / f"{m['name']}.py").is_file():
            out.append(f"metric {m['name']}: no reader file")
    return out
