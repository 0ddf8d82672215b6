"""Helpers of the benchmark's tests: a copy of the benchmark at a size that
a CPU test run can hold."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

#: every cell's corpus cut to this many rows of this width for the CPU
TINY = {"n": 8192, "dim": 256, "pool": 256}


def tiny_root(dest: Path) -> Path:
    """A copy of the benchmark under ``dest`` whose configurations draw
    ``TINY`` corpora and whose traffic fits a one-second window on the
    CPU; limits, policies and everything else as committed."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copytree(ROOT / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (dest / "BENCHMARK.json").write_text(json.dumps(bench))
    for c in bench["configs"]:
        path = dest / c["file"]
        cfg = json.loads(path.read_text())
        cfg["data"].update(TINY)
        path.write_text(json.dumps(cfg))
    for path in (dest / "bench" / "traffic").glob("*.json"):
        t = json.loads(path.read_text())
        if t["loop"] == "open":
            t["rate_qps"] = 300
        else:
            t.update(outstanding=64, slots=32)
        path.write_text(json.dumps(t))
    return dest
