"""Reduce a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes one ``.xplane.pb`` per traced window.  Its device
planes (``/device:TPU:<n>``) hold a line of XLA programs (``XLA Modules``,
one event per executed program) and a line of the operations inside them
(``XLA Ops``, one event per executed HLO instruction, the Pallas kernels
among them as custom calls).  The host plane holds the benchmark's own
spans (``bench.submit``, ``bench.engine_step``, ``bench.gen_sleep``) on
the same clock.

:func:`events_from_xplane` turns a trace into plain event tuples, and
:class:`Trace` reduces them: busy time as the union of operation
intervals per chip, the idle gaps between them with the host span that
was open in each, and time and call counts per program and per operation.
A small recorded trace, already reduced to event tuples, is kept in
``bench/fixtures`` for the tests.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import json
import os
import re

#: host spans the load loop opens (bench/loop.py)
SPAN_PREFIX = "bench."
#: the span ``bench/run.py`` holds open for the measured window
WINDOW_SPAN = "bench.window"
_MODULE_RE = re.compile(r"^(.*?)(\(\d+\))?$")


@dataclasses.dataclass(frozen=True)
class Event:
    kind: str         # "op" | "module" | "span"
    chip: int         # device index; -1 for host spans
    name: str
    start: float      # seconds on the trace's clock
    dur: float


def op_label(text: str) -> str:
    """A stable short label for one XLA Ops event: the HLO instruction's
    name without its ``.<n>`` suffix.  A Pallas kernel's custom call is
    named after the kernel (``gqa_decode_paged``, ``fused_sample``);
    other operations read as their kind (``fusion``, ``copy``,
    ``dynamic-update-slice``...)."""
    name = text.split(" ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", name)


#: operations that contain others (a scanned layer loop): their time is
#: their body's, so they are left out of sums by label
CONTAINERS = ("while", "conditional", "call")


def module_label(name: str) -> str:
    """``jit_serve_sample_body(1605...)`` -> ``jit_serve_sample_body``."""
    return _MODULE_RE.match(name).group(1)


def events_from_xplane(path: str) -> list[Event]:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out: list[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            chip = int(plane.name.rsplit(":", 1)[1])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    kind = "op"
                elif line.name == "XLA Modules":
                    kind = "module"
                else:
                    continue
                for e in line.events:
                    name = op_label(e.name) if kind == "op" \
                        else module_label(e.name)
                    out.append(Event(kind, chip, name, e.start_ns * 1e-9,
                                     e.duration_ns * 1e-9))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        out.append(Event("span", -1, e.name,
                                         e.start_ns * 1e-9,
                                         e.duration_ns * 1e-9))
    return out


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _clip(events: list[Event], lo: float, hi: float) -> list[Event]:
    """The parts of ``events`` that lie inside ``[lo, hi]``."""
    out = []
    for e in events:
        a, b = max(e.start, lo), min(e.start + e.dur, hi)
        if b > a or (b == a and lo <= e.start <= hi):
            out.append(dataclasses.replace(e, start=a, dur=b - a))
    return out


class Trace:
    """A reduced trace.  Where it holds a ``bench.window`` span, only what
    lies inside that span counts, clipped to it; otherwise the window runs
    from the first event to the last."""

    def __init__(self, events: list[Event], n_devices: int = 1):
        marks = [e for e in events
                 if e.kind == "span" and e.name == WINDOW_SPAN]
        if marks:
            lo, hi = marks[0].start, marks[0].start + marks[0].dur
            events = _clip([e for e in events if e.name != WINDOW_SPAN],
                           lo, hi)
        self.events = events
        self.n_devices = n_devices
        ops = [e for e in events if e.kind == "op"]
        spans = [e for e in events if e.kind == "span"]
        if not ops:
            raise ValueError("the trace holds no device operation")
        if marks:
            self.t0, self.t1 = lo, hi
        else:
            self.t0 = min(e.start for e in ops + spans)
            self.t1 = max(e.start + e.dur for e in ops + spans)
        #: length of the traced window, seconds
        self.window_s = self.t1 - self.t0
        busy = []
        self._busy_by_chip = {}
        for chip in range(n_devices):
            u = _union([(e.start, e.start + e.dur) for e in ops
                        if e.chip == chip])
            self._busy_by_chip[chip] = u
            busy.append(sum(b - a for a, b in u))
        #: seconds in which an operation ran, averaged over the chips
        self.busy_s = sum(busy) / n_devices
        self._spans = sorted(spans, key=lambda e: e.start)
        self._span_starts = [s.start for s in self._spans]

    # -- time by name -----------------------------------------------------------
    def _sum(self, kind: str, match) -> tuple[int, float]:
        n, t = 0, 0.0
        for e in self.events:
            if e.kind == kind and match(e.name):
                n += 1
                t += e.dur
        return n, t

    def ops(self, name: str) -> tuple[int, float]:
        """(calls, device seconds) of the operations labelled ``name``,
        over all chips."""
        return self._sum("op", lambda n: n == name)

    def modules(self, name: str) -> tuple[int, float]:
        """(calls, device seconds) of the programs named ``name``."""
        return self._sum("module", lambda n: n == name)

    # -- idle gaps --------------------------------------------------------------
    def span_at(self, t: float) -> str:
        """The innermost benchmark span open at ``t`` on the host."""
        best = None
        i = bisect.bisect_right(self._span_starts, t)
        for s in reversed(self._spans[:i]):
            if s.start <= t <= s.start + s.dur:
                if best is None or s.dur < best.dur:
                    best = s
            if best is not None and s.start + 600.0 < t:
                break
        return best.name if best else "outside the benchmark's spans"

    def idle_gaps(self, chip: int = 0) -> list[tuple[float, str]]:
        """Every idle gap of ``chip`` inside the window, longest first,
        each with the host span open at its middle."""
        u = self._busy_by_chip[chip]
        gaps = []
        prev = self.t0
        for a, b in u + [(self.t1, self.t1)]:
            if a > prev:
                gaps.append((a - prev, self.span_at((a + prev) / 2)))
            prev = max(prev, b)
        return sorted(gaps, reverse=True)

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time (by program and
        label), and the longest idle gaps by what the host was doing."""
        by: dict[str, float] = {}
        mods = sorted((e for e in self.events if e.kind == "module"),
                      key=lambda e: (e.chip, e.start))
        starts = {c: [m.start for m in mods if m.chip == c]
                  for c in range(self.n_devices)}
        per_chip = {c: [m for m in mods if m.chip == c]
                    for c in range(self.n_devices)}
        for e in self.events:
            if e.kind != "op" or e.name in CONTAINERS:
                continue
            i = bisect.bisect_right(starts.get(e.chip, []), e.start) - 1
            mod = per_chip[e.chip][i].name if i >= 0 else "?"
            key = f"{mod}/{e.name}"
            by[key] = by.get(key, 0.0) + e.dur
        ops = sorted(by.items(), key=lambda kv: -kv[1])[:top]
        gaps = self.idle_gaps()[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[name, s] for s, name in gaps]}


def load(trace_dir: str, n_devices: int = 1) -> Trace:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return Trace(events_from_xplane(max(paths, key=os.path.getmtime)),
                 n_devices)


# -- fixtures -------------------------------------------------------------------
def save_events(events: list[Event], path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump([dataclasses.astuple(e) for e in events], f)


def load_events(path: str) -> list[Event]:
    with gzip.open(path, "rt") as f:
        return [Event(*row) for row in json.load(f)]
