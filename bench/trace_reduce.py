"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy time, idle
gaps and device time per kernel or per program.

As a TPU v5e writes it (jax 0.9), a device plane is named
``/device:TPU:<id>``. Its ``XLA Ops`` line holds one event per executed HLO
operation, named by the operation's HLO text (``%block_mips.2 = (f32[...``)
and carrying no module stat; its ``XLA Modules`` line holds one event per
executed program (``jit__verify(<hash>)``), so an operation belongs to the
module event that covers it. A Pallas kernel is one custom-call event,
whatever it does inside; a walk that the program splits into a chain of
calls shows as one event per call. The ``Async XLA Ops`` line holds copies
that overlap other work and is not counted as busy. Host planes
(``/host:CPU``) hold the benchmark's `jax.profiler.TraceAnnotation` spans,
the program's spans where they are annotated, and JAX's dispatch events;
they tell what the host was doing while the device sat idle.

All times are nanoseconds on the profiler's one clock.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Callable, Iterable, NamedTuple, Optional

import numpy as np

DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "/host:"
WINDOW = "bench.window"
# the verify kernel's custom calls, one per call of a chained walk
KERNEL = re.compile(r"%block_mips(\.\d+)? = ")


class Event(NamedTuple):
    name: str
    start: float
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


def find_xplane(log_dir: str) -> str:
    """The newest ``*.xplane.pb`` under a profiler log directory."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def _events(line) -> list:
    return [Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


class Trace:
    """Device and host events of one profile, indexed for the reductions."""

    def __init__(self, profile):
        self.device: dict = {}          # plane name -> [Event] (XLA Ops)
        self.modules: dict = {}         # plane name -> [Event] (XLA Modules)
        self.host: list = []            # the window's thread, else all host
        window_thread = None
        for plane in profile.planes:
            if plane.name.startswith(DEVICE_PREFIX):
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        self.device[plane.name] = _events(line)
                    elif line.name == MODULES_LINE:
                        self.modules[plane.name] = _events(line)
            elif plane.name.startswith(HOST_PREFIX):
                for line in plane.lines:
                    evs = _events(line)
                    if any(e.name == WINDOW for e in evs):
                        window_thread = evs
                    self.host.extend(evs)
        if window_thread is not None:   # what the benchmark's thread did
            self.host = window_thread
        for evs in self.modules.values():
            evs.sort(key=lambda e: e.start)
        self._module_starts = {p: [e.start for e in evs]
                               for p, evs in self.modules.items()}
        self._host_start = np.array([e.start for e in self.host], float)
        self._host_end = np.array([e.end for e in self.host], float)

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData

        return cls(ProfileData.from_file(path))

    @classmethod
    def from_text_proto(cls, text: str) -> "Trace":
        from jax.profiler import ProfileData

        return cls(ProfileData.from_text_proto(text))

    def window(self, name: str = WINDOW) -> tuple:
        """(start, end) of the host span ``name`` (the measured window)."""
        spans = [e for e in self.host if e.name == name]
        if not spans:
            raise ValueError(f"no host span {name!r} in the trace")
        return min(e.start for e in spans), max(e.end for e in spans)

    def ops(self, lo: float, hi: float) -> dict:
        """Device plane -> its op events clipped to [lo, hi]."""
        return {p: clip(evs, lo, hi) for p, evs in self.device.items()}

    def module_time_ns(self, lo: float, hi: float, prefixes: tuple) -> dict:
        """Device plane -> summed time of the module events clipped to
        [lo, hi] whose name starts with one of ``prefixes``."""
        return {p: sum(e.dur for e in clip(evs, lo, hi)
                       if e.name.startswith(prefixes))
                for p, evs in self.modules.items()}

    def module_of(self, plane: str, e: Event) -> str:
        """Name of the module event covering ``e``, without its hash."""
        i = bisect.bisect_right(self._module_starts.get(plane, []), e.start)
        if i and self.modules[plane][i - 1].end > e.start:
            return self.modules[plane][i - 1].name.split("(")[0]
        return "?"

    def host_doing(self, a: float, b: float) -> Optional[str]:
        """Name of the innermost host span (other than the window) that
        covers the middle of [a, b]."""
        mid = 0.5 * (a + b)
        cover = np.nonzero((self._host_start <= mid)
                           & (self._host_end >= mid))[0]
        cover = [i for i in cover if self.host[i].name != WINDOW]
        if not cover:
            return None
        return self.host[min(cover, key=lambda i: self.host[i].dur)].name


def clip(events: Iterable[Event], lo: float, hi: float) -> list:
    out = []
    for e in events:
        a, b = max(e.start, lo), min(e.end, hi)
        if b > a:
            out.append(e._replace(start=a, end=b))
    return out


def union(intervals: Iterable[tuple]) -> list:
    """Merged, sorted, disjoint intervals covering the same points."""
    merged: list = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return [tuple(m) for m in merged]


def busy_ns(events: Iterable[Event]) -> float:
    """Time in which at least one of ``events`` runs (overlaps count once)."""
    return sum(b - a for a, b in union((e.start, e.end) for e in events))


def gaps(events: Iterable[Event], lo: float, hi: float) -> list:
    """Idle intervals of [lo, hi] in which none of ``events`` runs."""
    out, at = [], lo
    for a, b in union((e.start, e.end) for e in events):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def time_ns(events: Iterable[Event], pred: Callable[[Event], bool]) -> float:
    """Summed device duration of the events ``pred`` accepts."""
    return sum(e.dur for e in events if pred(e))


def kernel_ns(run) -> Optional[float]:
    """Per-chip mean device time of the verify kernel's calls in a run's
    traced window, or None where the trace holds none."""
    if run.trace is None:
        return None
    ops = run.trace.ops(run.lo, run.hi)
    if not ops:
        return None
    ns = sum(time_ns(evs, lambda e: KERNEL.match(e.name))
             for evs in ops.values()) / len(ops)
    return ns or None


def op_name(e: Event) -> str:
    """The HLO operation's name, without its text (``%block_mips.2``)."""
    return e.name.split(" = ")[0]


def breakdown(trace: Trace, lo: float, hi: float, top: int = 10) -> dict:
    """The device ops that took most time, by ``module/op``, and the longest
    idle gaps by what the host was doing, each as [name, seconds]. Both are
    over every device plane of the window."""
    per_op: dict = defaultdict(float)
    idle: list = []
    for plane, evs in trace.ops(lo, hi).items():
        for e in evs:
            per_op[f"{trace.module_of(plane, e)}/{op_name(e)}"] += e.dur
        idle.extend(gaps(evs, lo, hi))
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    idle.sort(key=lambda g: g[0] - g[1])
    named = [[trace.host_doing(a, b) or "no host span", (b - a) / 1e9]
             for a, b in idle[:top]]
    return {"device_ops": [[k, v / 1e9] for k, v in ops],
            "idle_gaps": named}
