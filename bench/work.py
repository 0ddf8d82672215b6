"""Work that a kernel has to do, from the cell's shapes, and the device
peaks it is held against.

The stage-1 screen of the streaming engine reads each corpus row's lead
``d1`` float32 dims once per query chunk and multiplies them with the
chunk's real queries (Zhang et al., "Distance Comparison Operations Are Not
Silver Bullets in Vector Similarity Search", the partial-distance screen of
PDScanning+).  The work is counted from the rows, ``d1`` and the real
queries, never from the kernel's padded tile or its outputs, so a fused or
renamed implementation of the same screen reads against the same work.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, path=PEAKS) -> dict:
    """Peaks of one chip of ``device_kind``; an unknown kind is an error."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def shard_rows(n: int, chips: int) -> int:
    """Corpus rows each chip holds and scans: ``n`` rows divided over the
    cell's ``chips``, as the program's row-sharded scan divides them
    (``bench.manifest`` holds ``n`` to a multiple of ``chips``)."""
    return n // chips


def screen_work(rows: int, d1: int, queries: int, query_chunk: int):
    """(flop, bytes) of the stage-1 screen for ``queries`` real queries
    over ``rows`` rows: ``2 * rows * d1`` flop per query, and the lead dims
    ``rows * d1 * 4`` bytes read once per query chunk."""
    chunks = math.ceil(queries / query_chunk)
    return 2.0 * rows * d1 * queries, 4.0 * rows * d1 * chunks


def least_time_s(flop: float, nbytes: float, peak: dict):
    """The least time the chip could take, and what bounds it
    (``"bytes"`` or ``"flop"``)."""
    t_flop = flop / peak["flops_bf16"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flop else (t_flop, "flop")
