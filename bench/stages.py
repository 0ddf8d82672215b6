"""The program's own spans and device scopes in a profiler trace, and the
per-stage reductions that read them.

The harness records its own spans (``bench.*``).  The program records host
spans of its own (``search.*``) and names each stage of the streaming
engine with a device scope (``dco.*``), which lands in the ``op_name``
metadata of the operations the stage traced into (``repro.utils.spans``
lists both).  ``load`` reads a trace into the device operations of each
chip by name, both kinds of span in ``spans``, and each device operation's
``op_name`` in ``scopes``, parallel to ``ops``.  The op tuples are
``(name, t0, t1)``, so every reduction of ``bench.tracing`` reads a
``ProgramTrace`` as it reads a ``Trace``; ``idle_gaps`` then names a gap by
the innermost program span that holds it.  Like those reductions, each
here is defined over the chips the cell used (``tracing.used_chips``): a
time is the mean over them of each chip's own.

On a TPU the op's ``op_name`` is the ``tf_op`` stat of the op event's
metadata, which ``jax.profiler.ProfileData`` does not expose; the few
protobuf fields that lead to it are decoded here, with no protobuf
package.  A CPU trace has no TPU plane and so no scopes.

On a trace of a program that records no spans and scopes, ``scope_ms``,
``span_ms`` and ``idle_in`` return None.

    python3 bench/stages.py --workload <cell> --seed <n> [--seconds <s>]
        [--excerpt <path>]

runs a cell's set-up, an untraced window and a traced stretch on the chip
and prints the stage breakdown as JSON (``--excerpt`` also writes a cut of
the trace around one step boundary, for the tests).
"""
from __future__ import annotations

import bisect
import functools
import sys
from dataclasses import dataclass, field
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[0] = str(ROOT)         # not bench/: its modules are bench.*
    sys.path.insert(1, str(ROOT / "src"))

from bench import tracing  # noqa: E402

SPAN_PREFIXES = ("bench.", "search.")
SCOPE_PREFIX = "dco."
OP_NAME_STAT = "tf_op"      # XProf's name for the op_name metadata


@dataclass
class ProgramTrace(tracing.Trace):
    """A ``tracing.Trace`` with the program's spans among ``spans`` and the
    ``op_name`` of each device operation ("" where the trace has none)."""

    scopes: dict = field(default_factory=dict)  # device -> [op_name]
    #: ``scope_own_ms`` of this trace, once ``scope_ms`` has read it
    own_ms: dict | None = field(default=None, compare=False, repr=False)


# -- protobuf wire format ----------------------------------------------------

def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf):
    """(field number, value) of each field of one protobuf message: an int
    for varints, a memoryview for length-delimited fields, raw bytes for
    fixed-width ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            val, i = bytes(buf[i:i + size]), i + size
        else:
            raise ValueError(f"protobuf wire type {wire} is not supported")
        yield key >> 3, val


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _first(buf, number: int, default=None):
    return next((v for f, v in _fields(buf) if f == number), default)


def op_names(xspace) -> dict:
    """{device plane: [(event name, op_name) of each event of its "XLA Ops"
    line, in order]} of a serialized ``XSpace``.  The op_name is the
    ``tf_op`` stat of the event's metadata, without XProf's trailing
    ``:type``.  Fields read: XSpace 1 planes; XPlane 2 name, 3 lines, 4
    event metadata, 5 stat metadata (maps: 1 key, 2 value); XLine 2 name,
    4 events; XEvent 1 metadata id; XEventMetadata 2 name, 5 stats;
    XStatMetadata 2 name; XStat 1 metadata id, 5 string, 7 reference to a
    stat metadata whose name is the string."""
    out = {}
    for f, plane in _fields(xspace):
        if f != 1:
            continue
        fields = list(_fields(plane))
        name = _text(next((v for g, v in fields if g == 2), b""))
        if not (name.startswith("/device:") and "TPU" in name):
            continue
        stat_names = {}
        for g, v in fields:
            if g == 5:
                meta = _first(v, 2, b"")
                stat_names[_first(meta, 1, 0)] = _text(_first(meta, 2, b""))
        op_stat = [k for k, n in stat_names.items() if n == OP_NAME_STAT]
        events = {}
        for g, v in fields:
            if g != 4:
                continue
            meta = _first(v, 2, b"")
            ev_name, op = "", ""
            for h, w in _fields(meta):
                if h == 2:
                    ev_name = _text(w)
                elif h == 5 and _first(w, 1, 0) in op_stat:
                    ref = _first(w, 7)
                    op = (stat_names.get(ref, "") if ref is not None
                          else _text(_first(w, 5, b"")))
            events[_first(meta, 1, 0)] = (ev_name, op.removesuffix(":"))
        for g, line in fields:
            if g == 3 and _text(_first(line, 2, b"")) == tracing.OPS_LINE:
                out[name] = [events.get(m, ("", ""))
                             for m in _event_ids(line)]
    return out


def _event_ids(line) -> list:
    """The metadata id of each event (field 4) of one serialized XLine, in
    order.  An XEvent writes its id (field 1) first, so only its first
    varint is read; the generic walk is kept for an event that does not."""
    out = []
    i, n = 0, len(line)
    while i < n:
        key, i = _varint(line, i)
        wire = key & 7
        if wire == 0:
            i = _varint(line, i)[1]
        elif wire == 2:
            size, i = _varint(line, i)
            if key >> 3 == 4:
                out.append(_varint(line, i + 1)[0]
                           if size and line[i] == 0x08
                           else _first(line[i:i + size], 1, 0))
            i += size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"protobuf wire type {wire} is not supported")
    return out


# -- reading a trace -----------------------------------------------------------

def load(trace_dir) -> ProgramTrace:
    """Read the one ``.xplane.pb`` under ``trace_dir``: each TPU plane's
    "XLA Ops" events, op names cut from their HLO text
    (``tracing.op_name``), with each one's ``op_name``, and the host's
    ``bench.*`` and ``search.*`` spans with their arguments."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(files)}")
    raw = files[0].read_bytes()
    names = op_names(memoryview(raw))
    tr = ProgramTrace()
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        is_dev = plane.name.startswith("/device:") and "TPU" in plane.name
        for line in plane.lines:
            if is_dev and line.name == tracing.OPS_LINE:
                ev = list(line.events)
                ops = [(tracing.op_name(e.name), int(e.start_ns),
                        int(e.start_ns + e.duration_ns)) for e in ev]
                meta = names.get(plane.name, [])
                if [m[0] for m in meta] != [e.name for e in ev]:
                    raise RuntimeError(f"{plane.name}: op events and their "
                                       "metadata do not line up")
                tr.ops[plane.name] = ops
                tr.scopes[plane.name] = [m[1] for m in meta]
            elif not is_dev:
                tr.spans += [(e.name, int(e.start_ns),
                              int(e.start_ns + e.duration_ns), dict(e.stats))
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIXES)]
    tr.spans.sort(key=lambda s: s[1])
    return tr


@functools.cache          # a trace repeats a few thousand op_names
def scope_of(op_name: str):
    """The innermost ``dco.*`` scope of an ``op_name`` path, or None."""
    parts = [p for p in op_name.split("/") if p.startswith(SCOPE_PREFIX)]
    return parts[-1] if parts else None


# -- reductions ----------------------------------------------------------------

def _chips(tr) -> list:
    """[(ops, op_names)] of the chips the cell used (``tracing.used_chips``);
    a plain ``Trace`` has no op_names."""
    scopes = getattr(tr, "scopes", {})
    return [(ev, scopes.get(dev, []))
            for dev, ev in tracing.used_chips(tr).items()]


def scope_own_ms(tr: ProgramTrace) -> dict:
    """{scope (None: outside every ``dco.*`` scope): mean own ms per whole
    ``bench.step`` span and chip}: each operation's time without the
    operations nested in it, as ``tracing.self_times`` counts it, so a
    ``while`` or a ``conditional`` is not counted twice.  Empty without
    scoped ops or whole steps."""
    chips = _chips(tr)
    steps = tr.step_spans()
    if not steps or not any(n and scope_of(n)
                            for _, names in chips for n in names):
        return {}
    tot: dict = {}
    for ops, names in chips:
        labelled = [(scope_of(n), a, b) for (_, a, b), n in zip(ops, names)]
        tot.update((scope, tot.get(scope, 0)) for scope, _, _ in labelled)
        for s, ev in zip(steps, tracing.per_span(labelled, steps)):
            for scope, ns in tracing.self_times(ev, s[1], s[2]).items():
                tot[scope] += ns
    return {scope: ns / len(chips) / 1e6 / len(steps)
            for scope, ns in tot.items()}


def scope_ms(tr: ProgramTrace, scope: str):
    """Mean own time, ms, of the operations under ``scope`` per whole
    ``bench.step`` span; None where no operation of the trace carries it.
    The reduction runs once per trace, for all the stage readers."""
    if getattr(tr, "own_ms", None) is None:     # a plain Trace has none
        tr.own_ms = scope_own_ms(tr)
    return tr.own_ms.get(scope)


def _in_steps(tr, name: str):
    """[(step, [spans named ``name`` inside it])] for each whole
    ``bench.step`` span, or None where the trace has no such span."""
    steps = tr.step_spans()
    named = [s for s in tr.spans if s[0] == name]
    if not steps or not named:
        return None
    return [(s, [n for n in named if n[1] >= s[1] and n[2] <= s[2]])
            for s in steps]


def span_ms(tr, name: str):
    """Mean summed duration, ms, of the spans named ``name`` per whole
    ``bench.step`` span; None where the trace has no such span."""
    per = _in_steps(tr, name)
    if per is None:
        return None
    return sum(b - a for _, spans in per for _, a, b, _ in spans) \
        / 1e6 / len(per)


def idle_in(tr, name: str):
    """Mean device-idle time, ms, inside the spans named ``name`` per whole
    ``bench.step`` span and chip; None where the trace has no such span or
    no chip ran anything."""
    per = _in_steps(tr, name)
    chips = tracing.used_chips(tr)
    if per is None or not chips:
        return None
    idle = 0
    for ops in chips.values():
        busy = tracing.union(ops)
        starts = [s for s, _ in busy]
        idle += sum(b - a - _overlap(busy, starts, a, b)
                    for _, spans in per for _, a, b, _ in spans)
    return idle / len(chips) / 1e6 / len(per)


def _overlap(busy, starts, a: int, b: int) -> int:
    """Length of sorted, disjoint ``busy`` (starting at ``starts``) inside
    [a, b]."""
    j = max(0, bisect.bisect_right(starts, a) - 1)
    out = 0
    for s, e in busy[j:]:
        if s >= b:
            break
        out += max(0, min(e, b) - max(s, a))
    return out


def idle_coverage(tr, parent: str = "search.step"):
    """(idle ns inside ``parent`` spans, of it the ns inside one of the
    parent's child spans), whole spans in the window, mean over the chips
    the cell used."""
    chips = tracing.used_chips(tr)
    w0, w1 = tr.window()
    parents = [(p, tracing.union([s[:3] for s in tr.spans if s is not p
                                  and s[1] >= p[1] and s[2] <= p[2]]))
               for p in tr.spans
               if p[0] == parent and p[1] >= w0 and p[2] <= w1]
    total = covered = 0
    for ops in chips.values():
        busy = tracing.union(ops)
        for p, kids in parents:
            idle = _minus([[p[1], p[2]]], busy)
            total += sum(b - a for a, b in idle)
            covered += sum(b - a for a, b in _clip(idle, kids))
    n = max(len(chips), 1)
    return total / n, covered / n


def _clip(spans, keep) -> list:
    """The parts of sorted, disjoint ``spans`` inside sorted, disjoint
    ``keep``."""
    out = []
    for a, b in spans:
        for c, d in keep:
            lo, hi = max(a, c), min(b, d)
            if hi > lo:
                out.append([lo, hi])
    return out


def _minus(spans, cut) -> list:
    """The parts of sorted, disjoint ``spans`` outside sorted, disjoint
    ``cut``."""
    out = []
    for a, b in spans:
        t = a
        for c, d in cut:
            if d <= t or c >= b:
                continue
            if c > t:
                out.append([t, c])
            t = max(t, d)
        if t < b:
            out.append([t, b])
    return out


# -- one cell's stage breakdown on the chip ------------------------------------

#: per-layer metrics that read the program's spans and scopes
STAGE_METRICS = ("engine.lead_ms.bulk", "engine.compact_ms.bulk",
                 "engine.tail_ms.bulk", "engine.merge_ms.bulk",
                 "backend.prep_ms.bulk", "backend.fetch_idle_ms.bulk")
#: the engine's stages, whose own time should make up its device time
STAGES = ("dco.seed", "dco.lead", "dco.compact", "dco.tail", "dco.merge")
EXCERPT_PAD_NS = 300_000


def breakdown(tr: ProgramTrace, ctx, root) -> dict:
    """What one traced stretch says per stage and per span."""
    from bench import manifest
    own = scope_own_ms(tr)
    device = manifest.reader(root, "engine.device_ms.bulk")(ctx)
    chips = _chips(tr)
    rest: dict = {}
    steps = tr.step_spans()
    for ops, names in chips:
        labelled = [((scope_of(n), o[0]), o[1], o[2])
                    for o, n in zip(ops, names)]
        for s, ev in zip(steps, tracing.per_span(labelled, steps)):
            for key, ns in tracing.self_times(ev, s[1], s[2]).items():
                if key[0] not in STAGES:
                    key = f"{key[0]}:{key[1]}"
                    rest[key] = rest.get(key, 0) + ns
    per_ms = 1e6 * len(steps) * len(chips)
    idle, covered = idle_coverage(tr) if tr.spans else (0, 0)
    span_names = sorted({s[0] for s in tr.spans
                         if s[0].startswith("search.")})
    return {
        "metrics": {m: manifest.reader(root, m)(ctx) for m in STAGE_METRICS},
        "engine.device_ms.bulk": device,
        "scope_own_ms": {str(k): v for k, v in own.items()},
        "stages_share_of_device": (sum(own.get(s, 0.0) for s in STAGES)
                                   / device if device and own else None),
        "rest_by_op_ms": sorted(([k, v / per_ms] for k, v in rest.items()
                                 if v > 0), key=lambda kv: -kv[1])[:12],
        "span_ms": {n: span_ms(tr, n) for n in span_names},
        "idle_in_ms": {n: idle_in(tr, n) for n in span_names},
        "search_step_idle_ms": idle / 1e6,
        "search_step_idle_in_children": covered / idle if idle else None,
        "gaps_over_1ms": [g for g in tracing.idle_gaps(tr, n=10_000)
                          if g[1] >= 1e-3],
        "traced_step_ms": (sum(s[2] - s[1] for s in steps) / 1e6
                           / len(steps) if steps else None),
    }


def excerpt(tr: ProgramTrace, note: str) -> dict:
    """A cut of the first chip's part of ``tr`` around the first boundary
    between two whole steps of the window: from a little before the first
    step's last device operation ends to a little after the next step's
    first one starts (or its seed sync ends).  Operations keep their
    times; spans are cut at the slice's edges, and a ``bench.window`` marks
    the slice."""
    dev = next(iter(tr.ops))
    ops, names = tr.ops[dev], tr.scopes[dev]
    s0, s1 = tr.step_spans()[:2]
    go = next(s for s in tr.spans if s[0] == "search.dispatch"
              and s[1] >= s1[1])
    sync = [s[2] for s in tr.spans if s[0] == "search.seed_sync"
            and go[1] <= s[1] <= go[2]]
    lo = max(b for _, a, b in ops if s0[1] <= a < s0[2]) - EXCERPT_PAD_NS
    hi = max([min(a for _, a, _ in ops if a >= go[1])] + sync) \
        + EXCERPT_PAD_NS
    keep = [j for j, (_, a, b) in enumerate(ops) if a < hi and b > lo]
    spans = [[n, max(a, lo), min(b, hi), st] for n, a, b, st in tr.spans
             if a < hi and b > lo and n != "bench.window"]
    return {"recorded": note, "device": dev,
            "ops": [list(ops[j]) for j in keep],
            "scopes": [names[j] for j in keep],
            "spans": spans + [["bench.window", lo, hi, {}]]}


def from_excerpt(d: dict) -> ProgramTrace:
    """The ``ProgramTrace`` of an excerpt written by ``excerpt``."""
    return ProgramTrace(ops={d["device"]: [tuple(e) for e in d["ops"]]},
                        scopes={d["device"]: list(d["scopes"])},
                        spans=sorted((tuple(s) for s in d["spans"]),
                                     key=lambda s: s[1]))


def main(argv=None) -> int:
    import argparse
    import json

    import numpy as np
    from bench import driver, run, work

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--excerpt", default=None)
    args = ap.parse_args(argv)
    p = run.prepare(ROOT, args.workload, args.seed)
    try:
        rng = np.random.default_rng([args.seed, 0])
        window = driver.run_window(p.svc, p.pool, p.cell.traffic,
                                   args.seconds, rng)
        tr = run.traced_stretch(p.svc, p.pool, p.cell.traffic, rng,
                                len(p.devs))
    finally:
        p.clock.close()
    dev = p.devs[0]
    ctx = run.Context(window, tr, p.cell.config, p.cell.traffic,
                      work.peaks(dev.device_kind))
    out = {"workload": args.workload, "seed": args.seed,
           "device": f"{dev.platform} {dev.device_kind}",
           "window_step_ms": 1e3 * sum(s.t1 - s.t0 for s in window.steps)
           / len(window.steps),
           "window_qps": window.qps(), **breakdown(tr, ctx, ROOT)}
    # the profiler's cost: a traced step's wall over an untraced one's
    out["on_cost"] = out["traced_step_ms"] / out["window_step_ms"] - 1.0
    if args.excerpt:
        note = (f"{dev.device_kind}, cell {args.workload}, seed "
                f"{args.seed}: the end of one bench.step and the start of "
                "the next, spans cut at the slice's edges; bench.window "
                "marks the slice")
        Path(args.excerpt).write_text(json.dumps(excerpt(tr, note)))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
