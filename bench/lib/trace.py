"""Reduction of a profiler trace to device busy time and program time.

The JAX profiler writes an ``.xplane.pb``; :func:`load_events` flattens
it into :class:`Event` records (plane, line, name, start, duration).
:func:`reduce_events` then works on those records alone, so a small
synthetic list checks it:

* the traced window is the host span named ``window`` (the harness
  wraps its measured window in ``jax.profiler.TraceAnnotation``);
* device operations are the events on the ``ops`` line of every plane
  whose name starts with ``device_prefix``; busy time is the union of
  their intervals inside the window, averaged over the devices;
* program time is the duration of each compiled program's run on the
  ``modules`` line, inside the window, summed by :func:`classify` of the
  program's name (``prefill``, ``decode`` or ``other``);
* ``device_ops`` are the operations that took most time, and
  ``idle_gaps`` the first device's idle time inside the window, summed
  by the innermost host span (``bench.*``) open at each gap's middle.
"""
from __future__ import annotations

import bisect
import glob
import os
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Tuple

__all__ = ["Event", "load_events", "reduce_events", "classify", "union"]

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench.window"
TOP = 10


@dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load_events(trace_dir: str, *, device_prefix: str = DEVICE_PREFIX,
                lines: Tuple[str, ...] = (OPS_LINE, MODULES_LINE)
                ) -> List[Event]:
    """The events :func:`reduce_events` reads from the newest
    ``.xplane.pb`` under ``trace_dir``: the device planes' ``lines`` and
    the host spans named ``bench.*``."""
    import jax

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(max(paths, key=os.path.getmtime))
    out = []
    for plane in data.planes:
        dev = plane.name.startswith(device_prefix)
        for line in plane.lines:
            if dev and line.name not in lines:
                continue
            for ev in line.events:
                if dev or ev.name.startswith("bench."):
                    out.append(Event(plane.name, line.name, ev.name,
                                     float(ev.start_ns),
                                     float(ev.duration_ns)))
    return out


def classify(program: str) -> str:
    """The serving program a compiled module belongs to, by its name."""
    low = program.lower()
    for kind in ("prefill", "decode"):
        if kind in low:
            return kind
    return "other"


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(ev: Event, w0: float, w1: float) -> Optional[Tuple[float, float]]:
    a, b = max(ev.start_ns, w0), min(ev.end_ns, w1)
    return (a, b) if b > a else None


def _idle_by_span(busy: List[Tuple[float, float]], w0: float, w1: float,
                  host: List[Event]) -> List[Tuple[str, float]]:
    """Idle time of the device inside ``[w0, w1]``, summed by the
    innermost host span around each gap's midpoint, longest first."""
    host = sorted(host, key=lambda e: e.start_ns)
    starts = [e.start_ns for e in host]
    edges = [w0] + [x for ab in busy for x in ab] + [w1]
    out: Dict[str, float] = {}
    for a, b in zip(edges[::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        name = "host: no bench span"
        i = bisect.bisect_right(starts, mid) - 1
        # spans on one thread nest: the latest-starting span that is
        # still open at ``mid`` is the innermost
        while i >= 0:
            if host[i].end_ns >= mid:
                name = host[i].name
                break
            i -= 1
        out[name] = out.get(name, 0.0) + (b - a)
    return sorted(out.items(), key=lambda kv: -kv[1])


def reduce_events(events: List[Event], *, window: str = WINDOW,
                  device_prefix: str = DEVICE_PREFIX, ops: str = OPS_LINE,
                  modules: str = MODULES_LINE) -> Dict:
    spans = [e for e in events if not e.plane.startswith(device_prefix)
             and e.name.startswith("bench.")]
    wins = [e for e in spans if e.name == window]
    if not wins:
        raise ValueError(f"no {window!r} span in the trace")
    w0, w1 = wins[0].start_ns, wins[0].end_ns
    devices = sorted({e.plane for e in events
                      if e.plane.startswith(device_prefix)})
    if not devices:
        raise ValueError(f"no device plane {device_prefix}* in the trace")
    busy_ns, op_ns, first_union = [], {}, []
    program_ns: Dict[str, float] = {"prefill": 0.0, "decode": 0.0,
                                    "other": 0.0}
    module_names: Dict[str, float] = {}
    for di, dev in enumerate(devices):
        iv = []
        for e in events:
            if e.plane != dev:
                continue
            c = _clip(e, w0, w1)
            if c is None:
                continue
            if e.line == ops:
                iv.append(c)
                op_ns[e.name] = op_ns.get(e.name, 0.0) + (c[1] - c[0])
            elif e.line == modules:
                program_ns[classify(e.name)] += c[1] - c[0]
                module_names[e.name] = module_names.get(e.name, 0.0) \
                    + (c[1] - c[0])
        u = union(iv)
        busy_ns.append(sum(b - a for a, b in u))
        if di == 0:
            first_union = u
    nd = len(devices)
    idle = _idle_by_span(first_union, w0, w1,
                         [x for x in spans if x.name != window])
    top_ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(busy_ns) / nd / 1e9,
        "devices": nd,
        "program_s": {k: v / nd / 1e9 for k, v in program_ns.items()},
        "modules_s": {k: v / nd / 1e9 for k, v in
                      sorted(module_names.items(), key=lambda kv: -kv[1])},
        "device_ops": [[n, v / nd / 1e9] for n, v in top_ops],
        "idle_gaps": [[n, v / 1e9] for n, v in idle[:TOP]],
    }
