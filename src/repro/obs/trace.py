"""Span tracer: ``with span("select_frontend"): ...`` (DESIGN.md §14).

Design constraints, in order:

1. **Disabled cost ~ one function call.** `span()` returns a shared no-op
   context manager when tracing is off (and the caller didn't force
   ``active=True``), so an instrumented hot path pays one global read, one
   branch and an empty ``with`` — a few hundred ns against search batches
   measured in milliseconds (the ci.sh obs guard holds this under 1%).
   Nothing here ever runs inside a jit trace: call sites are all
   host-orchestrated code, gated so the disabled path stays off the trace.

2. **Honest timings under jit need fencing.** JAX dispatches asynchronously:
   an un-fenced span around a jit call measures *enqueue* time, not device
   time — the cost surfaces in whichever later span first forces the value
   (a `np.asarray`, a `block_until_ready`). `sp.fence(x)` calls
   `jax.block_until_ready(x)` *only when fencing is configured on*
   (`enable(fence=True)`), so production tracing can stay async while
   benchmark/per-phase runs opt into sequential, attributable timings.
   Spans record whether they were fenced (`fenced` flag, exported in the
   Chrome trace args) so a reader can tell the two apart.

3. **Bounded storage, thread-safe.** Completed spans land in a ring buffer
   (default 8192) under a lock; a long-lived serve process can leave tracing
   on without unbounded growth. `total()` counts every span ever recorded.

4. **Every span says where it sits.** A span names its ``layer`` (``api``,
   ``dispatch``, ``plan`` or ``pull`` on the search path) and may carry
   keyword stats (counts at its boundary, e.g. a verify round's ``slots`` and
   ``union``). Recording, it also notes its ``parent`` (the enclosing open
   span on this thread) and a ``batch`` id that the outermost span of a call
   allocates and every nested span inherits, so one search's spans group.

`export_chrome_trace(path)` writes the standard ``{"traceEvents": [...]}``
JSON (``ph="X"`` complete events, µs timestamps) that chrome://tracing and
https://ui.perfetto.dev load directly; ``args`` carries the span's fields.
With ``annotate=True`` each span also enters a
`jax.profiler.TraceAnnotation` whose keyword stats are the same fields, so
spans line up with XLA events in a `jax.profiler.trace()` capture (SNIPPETS.md
snippet 3) and name their layer there.
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time
from typing import Optional

__all__ = ["span", "configure", "enable", "disable", "enabled", "fencing",
           "spans", "clear", "total", "export_chrome_trace"]

_lock = threading.Lock()
_enabled = False
_fence = False
_annotate = False
_capacity = 8192
_ring: list = []          # completed span dicts, append order, bounded
_total = 0                # every span ever recorded (monotonic)
_batches = itertools.count()   # ids allocated by outermost spans


class _Open(threading.local):
    def __init__(self):
        self.stack = []   # this thread's open spans, outermost first


_open = _Open()

_EPOCH_NS = time.perf_counter_ns()   # trace timestamps are relative to import


def configure(enabled: Optional[bool] = None, fence: Optional[bool] = None,
              annotate: Optional[bool] = None,
              capacity: Optional[int] = None) -> None:
    """Set any subset of the tracer's four knobs (None = leave unchanged)."""
    global _enabled, _fence, _annotate, _capacity
    with _lock:
        if enabled is not None:
            _enabled = bool(enabled)
        if fence is not None:
            _fence = bool(fence)
        if annotate is not None:
            _annotate = bool(annotate)
        if capacity is not None:
            if int(capacity) < 1:
                raise ValueError(f"capacity must be >= 1, got {capacity!r}")
            _capacity = int(capacity)
            del _ring[: max(0, len(_ring) - _capacity)]


def enable(fence: bool = False, annotate: bool = False) -> None:
    configure(enabled=True, fence=fence, annotate=annotate)


def disable() -> None:
    configure(enabled=False, fence=False, annotate=False)


def enabled() -> bool:
    return _enabled


def fencing() -> bool:
    return _fence


def clear() -> None:
    """Drop stored spans (does not reset `total()` — it is monotonic)."""
    with _lock:
        _ring.clear()


def total() -> int:
    return _total


def spans() -> list:
    """Completed spans (in the order they closed) as dicts:
    ``{name, t0_us, dur_us, tid, fenced, layer, parent, batch, stats}``
    (``parent`` is the enclosing span's name or None, ``stats`` a dict). A
    snapshot copy — safe to iterate while other threads keep tracing."""
    with _lock:
        return list(_ring)


def _record(rec: dict) -> None:
    global _total
    with _lock:
        _total += 1
        _ring.append(rec)
        if len(_ring) > _capacity:
            del _ring[: len(_ring) - _capacity]


class _NullSpan:
    """Shared no-op span: the entire disabled-path cost."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def fence(self, x):
        return x


_NULL = _NullSpan()


def _fields(layer, parent, batch, stats) -> dict:
    """A span's ``layer``, ``parent``, ``batch`` and stats, Nones left out:
    its profiler annotation's stats and its Chrome export ``args``."""
    out = {k: v for k, v in (("layer", layer), ("parent", parent),
                             ("batch", batch)) if v is not None}
    out.update(stats)
    return out


class _Span:
    __slots__ = ("name", "metric", "layer", "stats", "parent", "batch",
                 "_t0", "_fenced", "_ann")

    def __init__(self, name: str, metric: Optional[str],
                 layer: Optional[str], stats: dict):
        self.name = name
        self.metric = metric
        self.layer = layer
        self.stats = stats
        self._fenced = False
        self._ann = None

    def __enter__(self):
        stack = _open.stack
        outer = stack[-1] if stack else None
        self.parent = outer.name if outer is not None else None
        self.batch = outer.batch if outer is not None else next(_batches)
        stack.append(self)
        if _annotate:
            try:
                import jax
                self._ann = jax.profiler.TraceAnnotation(
                    self.name, **_fields(self.layer, self.parent, self.batch,
                                         self.stats))
                self._ann.__enter__()
            except Exception:       # profiler backend absent: spans still work
                self._ann = None
        self._t0 = time.perf_counter_ns()
        return self

    def fence(self, x):
        """Block on ``x`` (any pytree of jax arrays) iff fencing is on.
        Returns ``x`` either way, so call sites read naturally."""
        if _fence and x is not None:
            import jax
            jax.block_until_ready(x)
            self._fenced = True
        return x

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        _open.stack.remove(self)
        dur_us = (t1 - self._t0) / 1e3
        _record({"name": self.name,
                 "t0_us": (self._t0 - _EPOCH_NS) / 1e3,
                 "dur_us": dur_us,
                 "tid": threading.get_ident(),
                 "fenced": self._fenced,
                 "layer": self.layer,
                 "parent": self.parent,
                 "batch": self.batch,
                 "stats": self.stats})
        if self.metric is not None:
            from . import metrics
            metrics.histogram(self.metric).observe(dur_us)
        return False


def span(name: str, active: Optional[bool] = None,
         metric: Optional[str] = None, layer: Optional[str] = None,
         **stats):
    """Open a span. ``active=None`` follows the global switch; ``True``
    forces recording for this call (the `RuntimeConfig.obs` per-call
    opt-in), ``False`` forces the no-op. ``metric`` names a declared
    histogram (obs.metrics glossary) fed the span duration in µs. ``layer``
    names the span's layer (DESIGN.md §14); keyword ``stats`` are counts at
    the span's boundary, recorded and annotated with it."""
    if not (_enabled if active is None else active):
        return _NULL
    return _Span(name, metric, layer, stats)


def export_chrome_trace(path: str) -> str:
    """Write stored spans as Chrome trace-event JSON (Perfetto-loadable).
    Returns ``path``. One ``ph="X"`` complete event per span; ``args``
    carries the ``fenced`` flag, so un-fenced (enqueue-time) spans are
    distinguishable from honest device timings, and the span's ``layer``,
    ``parent``, ``batch`` and stats."""
    recs = spans()
    tids = {}
    events = []
    for r in recs:
        tid = tids.setdefault(r["tid"], len(tids))
        events.append({"name": r["name"], "ph": "X", "pid": 0, "tid": tid,
                       "ts": r["t0_us"], "dur": r["dur_us"],
                       "cat": "repro.obs",
                       "args": {"fenced": r["fenced"],
                                **_fields(r["layer"], r["parent"],
                                          r["batch"], r["stats"])}})
    doc = {"traceEvents": events, "displayTimeUnit": "ms",
           "otherData": {"exporter": "repro.obs.trace",
                         "span_count": len(events)}}
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    return path
