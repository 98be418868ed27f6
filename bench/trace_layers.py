"""Put a traced window's device idle time down to the program's layers, and
read the verify rounds' slot counts.

The program's spans (``repro.obs.trace``) name their layer: ``api`` (the
``Searcher.search`` facade), ``dispatch`` (device calls and their host
glue), ``plan`` (host numpy tile planning) and ``pull`` (device -> host
copies). In a traced run they are profiler annotations on the window's
host thread, and `trace_reduce.Trace.host` holds them by name and time. The
layer of each name, and each span's stats, are read from the program's own
span records (`program_spans`), which traced runs keep on: a name always
sits in one layer.

`idle_by_layer` sweeps each device plane's idle gaps in the window and, at
each instant, finds the innermost layered host span open then; an instant
with none is unattributed. `slot_fill` is the share of the verify rounds'
tile slots that the batch selected (``union`` / ``slots`` of each
``verify_round*`` span). A program whose spans carry no layer (one from
before layered spans) reads nothing.

All times are nanoseconds on the profiler's clock.
"""
from __future__ import annotations

from typing import Iterable, Optional

import trace_reduce

UNATTRIBUTED = "unattributed"
ROUNDS = ("verify_round1", "verify_round2")


def program_spans() -> list:
    """The program's completed span records in the order they closed (its
    tracer's bounded ring: the newest 8,192 by default)."""
    from repro.obs import trace

    return trace.spans()


def layers_of(records: Iterable[dict]) -> dict:
    """Span name -> layer, over the records that name one."""
    return {r["name"]: r["layer"] for r in records if r.get("layer")}


def innermost(spans: Iterable[tuple]) -> list:
    """Disjoint, sorted (start, end, layer) segments: at each instant the
    layer of the innermost of the nested ``(start, end, layer)`` spans open
    then (spans on one thread nest)."""
    segs: list = []
    stack: list = []
    at = None

    def emit(a, b, layer):
        if b > a:
            segs.append((a, b, layer))

    for s in sorted(spans, key=lambda s: (s[0], -s[1])):
        while stack and stack[-1][1] <= s[0]:
            top = stack.pop()
            emit(at, top[1], top[2])
            at = top[1]
        if stack:
            emit(at, s[0], stack[-1][2])
        stack.append(s)
        at = s[0]
    while stack:
        top = stack.pop()
        emit(at, top[1], top[2])
        at = top[1]
    return segs


def idle_by_layer(trace, lo: float, hi: float,
                  layers: dict) -> Optional[dict]:
    """Per-chip mean idle ns of the window [lo, hi] by the layer of the
    innermost layered host span open at each instant, plus
    ``UNATTRIBUTED`` for idle time under no layered span. None where the
    trace has no device plane or no layered span in the window."""
    ops = trace.ops(lo, hi)
    spans = [(e.start, e.end, layers[e.name])
             for e in trace_reduce.clip(trace.host, lo, hi)
             if e.name in layers]
    if not ops or not spans:
        return None
    segs = innermost(spans)
    out = dict.fromkeys(sorted(set(layers.values())), 0.0)
    out[UNATTRIBUTED] = 0.0
    for evs in ops.values():
        i = 0
        for a, b in trace_reduce.gaps(evs, lo, hi):
            out[UNATTRIBUTED] += b - a
            while i < len(segs) and segs[i][1] <= a:
                i += 1
            j = i
            while j < len(segs) and segs[j][0] < b:
                s_a, s_b, layer = segs[j]
                ns = min(b, s_b) - max(a, s_a)
                out[layer] += ns
                out[UNATTRIBUTED] -= ns
                j += 1
    return {k: v / len(ops) for k, v in out.items()}


def idle_ms(run, layers: tuple) -> Optional[float]:
    """Device idle ms per batch under the innermost layered spans of
    ``layers`` in a run's traced window, or None where nothing is read."""
    if run.trace is None or not run.window.batches:
        return None
    by = idle_by_layer(run.trace, run.lo, run.hi,
                       layers_of(program_spans()))
    if by is None:
        return None
    return sum(by.get(layer, 0.0) for layer in layers) / 1e6 \
        / run.window.batches


def last_batches(records: list, n: int) -> set:
    """Batch ids of the last ``n`` calls whose outermost spans are in
    ``records`` (a traced window's batches are the program's last calls)."""
    ids = sorted({r["batch"] for r in records
                  if r.get("parent") is None and r.get("batch") is not None})
    return set(ids[-n:]) if n > 0 else set()


def round_slots(records: list, batches: set) -> dict:
    """Round name -> [selected slots walked, slots walked], summed over the
    verify round spans of ``batches`` that carry both stats. A round's
    selected slots are its ``union``, or all its ``slots`` where a tile cap
    truncated the union."""
    out: dict = {}
    for r in records:
        stats = r.get("stats") or {}
        if (r["name"] in ROUNDS and r.get("batch") in batches
                and "slots" in stats and "union" in stats):
            acc = out.setdefault(r["name"], [0, 0])
            acc[0] += min(stats["union"], stats["slots"])
            acc[1] += stats["slots"]
    return out


def slot_fill(records: list, n_batches: int) -> Optional[float]:
    """Selected over walked slots, summed over both verify rounds of the
    last ``n_batches`` calls, or None where no round span carries the
    counts."""
    per = round_slots(records, last_batches(records, n_batches))
    slots = sum(s for _, s in per.values())
    if not slots:
        return None
    return sum(u for u, _ in per.values()) / slots
