"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's numbers.

Read with ``jax.profiler.ProfileData`` and nothing else.  A device is a
plane named ``/device:...`` that has an ``XLA Ops`` or ``XLA Modules``
line.  Its busy time is the union of the intervals of its ``XLA Ops``
events (``XLA Modules`` where a plane has no op line) inside the traced
window; its idle gaps are the rest of the window.  Time per module comes
from the ``XLA Modules`` line, with the program id that the runtime
appends to a module's name (``jit_f(12)``) taken off.

The window is the host span named ``window`` (the harness wraps its
measured window in a ``TraceAnnotation`` of that name); without one it is
the span of all device events.  Host spans are the other events of the
host planes, for labelling idle gaps.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

WINDOW_SPAN = "window"
_PROGRAM_ID = re.compile(r"\(\d+\)$")


def module_name(name: str) -> str:
    """A module event's name without its program id."""
    return _PROGRAM_ID.sub("", name)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of [lo, hi] that merged ``busy`` does not cover."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


@dataclass
class Device:
    name: str
    busy: List[Interval]                 # merged, in the window (ns)
    module_s: Dict[str, float] = field(default_factory=dict)
    module_n: Dict[str, int] = field(default_factory=dict)

    @property
    def busy_s(self) -> float:
        return length(self.busy) * 1e-9


@dataclass
class Summary:
    window: Interval                     # ns
    devices: List[Device]
    spans: List[Tuple[str, float, float]]  # host spans (name, start, end)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Busy seconds averaged over the devices."""
        return sum(d.busy_s for d in self.devices) / len(self.devices)

    def module_s(self) -> Dict[str, float]:
        """Device seconds per module, summed over the devices."""
        out: Dict[str, float] = {}
        for d in self.devices:
            for k, v in d.module_s.items():
                out[k] = out.get(k, 0.0) + v
        return out

    def module_n(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for d in self.devices:
            for k, v in d.module_n.items():
                out[k] = out.get(k, 0) + v
        return out

    def idle_gaps(self, n: int = 10, device: int = 0
                  ) -> List[Tuple[str, float]]:
        """The device's ``n`` longest idle gaps in the window, longest
        first, each named by the innermost host span that covers the
        gap's midpoint (``idle`` where none does)."""
        lo, hi = self.window
        longest = sorted(gaps(self.devices[device].busy, lo, hi),
                         key=lambda g: g[0] - g[1])[:n]
        out = []
        for s, e in longest:
            mid = 0.5 * (s + e)
            best = None
            for name, a, b in self.spans:
                if a <= mid <= b and name != WINDOW_SPAN and (
                        best is None or b - a < best[2] - best[1]):
                    best = (name, a, b)
            out.append((best[0] if best else "idle", (e - s) * 1e-9))
        return out


def _events(line) -> List[Tuple[str, float, float]]:
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for ev in line.events]


def reduce(pd) -> Summary:
    """Reduce a ``ProfileData`` to a :class:`Summary`."""
    spans: List[Tuple[str, float, float]] = []
    dev_planes = []
    for plane in pd.planes:
        lines = {ln.name: ln for ln in plane.lines}
        if plane.name.startswith("/device:") and (
                "XLA Ops" in lines or "XLA Modules" in lines):
            dev_planes.append((plane.name, lines))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans.extend(_events(ln))
    if not dev_planes:
        raise ValueError("the trace holds no device plane")
    window = [s for s in spans if s[0] == WINDOW_SPAN]
    if window:
        lo, hi = window[0][1], window[0][2]
    else:
        ev = [e for _, lines in dev_planes for ln in lines.values()
              for e in _events(ln)]
        lo, hi = min(e[1] for e in ev), max(e[2] for e in ev)
    devices = []
    for name, lines in sorted(dev_planes):
        busy_line = lines.get("XLA Ops") or lines["XLA Modules"]
        busy = union(clip(((s, e) for _, s, e in _events(busy_line)),
                          lo, hi))
        dev = Device(name, busy)
        mods = lines.get("XLA Modules")
        for ev_name, s, e in (_events(mods) if mods is not None else []):
            c = clip([(s, e)], lo, hi)
            if not c:
                continue
            m = module_name(ev_name)
            dev.module_s[m] = dev.module_s.get(m, 0.0) + length(c) * 1e-9
            dev.module_n[m] = dev.module_n.get(m, 0) + 1
        devices.append(dev)
    return Summary((lo, hi), devices, spans)


def load(trace_dir: str) -> Summary:
    """Reduce the one ``.xplane.pb`` that ``jax.profiler`` wrote under
    ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {len(paths)}")
    return reduce(ProfileData.from_file(paths[0]))


def top(d: Dict[str, float], n: int = 10) -> List[List[object]]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def share(bytes_moved: float, seconds: float, bytes_per_s: float
          ) -> Optional[float]:
    """A byte-bound kernel's share of its roofline, in percent: the least
    time the bytes need at peak bandwidth over the time taken."""
    if seconds <= 0 or bytes_moved <= 0:
        return None
    return 100.0 * bytes_moved / bytes_per_s / seconds
