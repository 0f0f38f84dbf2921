"""The program's own spans against the device trace.

With ``repro.obs.trace.Tracer(profiler=True)`` installed, the serving
engine, the compile cache and the generated dispatch write their spans
into the JAX profiler's trace as well as into the tracer's buffer.
:func:`load` reads them from an ``.xplane.pb`` beside the device
operations, each operation with the named scope of the model
(``jax.named_scope``) it ran under. A TPU profile names an operation by
its HLO instruction and carries no ``op_name``, so the scope is looked up
in the optimised HLO text of the programs that ran (:func:`op_names`).
:func:`reduce` works on those records alone, so small synthetic lists
check it:

* ``scope_s``: device self time of the operations of each scope, in the
  prefill and in the decode programs. An operation's self time is its
  duration less the union of the operations nested inside it on the same
  line (the layer scan's ``while`` holds the fusions of its body);
* ``idle_with_work_s``: the first device's idle time inside the window
  while a ``serve.step`` span was open, and ``idle_s`` all of it;
* ``accounted_s``: the union, inside the window, of ``serve.step``,
  ``bench.idle`` and ``bench.submit``: what the host was doing.

:func:`admit_waits` and :func:`setup_compile_s` read the tracer's buffer.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from .trace import (DEVICE_PREFIX, MODULES_LINE, OPS_LINE, WINDOW,
                    classify, union)

__all__ = ["Op", "SCOPES", "scope_of", "hlo_head", "op_names", "load",
           "reduce", "self_times", "admit_waits", "setup_compile_s"]

# the model's named scopes (repro/models/layers.py, transformer.py),
# innermost first where one name holds another
SCOPES = ("attn/qkv", "attn/cache_write", "attn/core", "attn/out", "mlp",
          "norm", "embed", "head")
# host spans kept: the benchmark's and the program's
HOST_PREFIXES = ("bench.", "serve.", "request", "dispatch", "compile.")


@dataclass(frozen=True)
class Op:
    """A device or host event, with the scope of a device operation."""
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    scope: str = ""

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def scope_of(op_name: str) -> str:
    """The model scope an HLO ``op_name`` runs under, or ``""``."""
    path = "/" + op_name + "/"
    for s in SCOPES:
        if f"/{s}/" in path:
            return s
    return ""


def hlo_head(instr: str) -> Optional[Tuple[str, str]]:
    """``(name, result type)`` of an HLO instruction, as the compiled
    program's text prints it and as a device operation's event is named
    (``%fusion.199 = bf16[2097152]{...} fusion(...)``; the operands are
    printed differently in the two)."""
    instr = instr.strip()
    if instr.startswith("ROOT "):
        instr = instr[5:]
    name, sep, rest = instr.partition(" = ")
    if not sep or not name.startswith("%") or " " in name:
        return None
    if rest.startswith("("):            # a tuple type: to its close
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                return name, rest[:i + 1]
        return None
    return name, rest.split(" ", 1)[0]


_OP_NAME = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')


def op_names(hlo_texts: Iterable[str]) -> Dict[Tuple[str, str], str]:
    """``op_name`` of each instruction of the compiled programs' HLO
    texts, by :func:`hlo_head`. An instruction whose scope differs between
    programs is left out, so that its operations count as unscoped
    rather than under a wrong scope."""
    out: Dict[Tuple[str, str], str] = {}
    clash = set()
    for text in hlo_texts:
        for line in text.splitlines():
            m = _OP_NAME.search(line)
            head = hlo_head(line) if m else None
            if head is None:
                continue
            if head in out and scope_of(out[head]) != scope_of(m.group(1)):
                clash.add(head)
            out.setdefault(head, m.group(1))
    return {k: v for k, v in out.items() if k not in clash}


def load(trace_dir: str,
         op_names: Optional[Dict[Tuple[str, str], str]] = None) -> List[Op]:
    """Host spans (:data:`HOST_PREFIXES`) and the device planes' operation
    and module lines of the newest ``.xplane.pb`` under ``trace_dir``. A
    TPU operation's event carries no ``op_name``; its scope comes from
    ``op_names`` (:func:`op_names` of the programs' HLO texts)."""
    import jax

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(max(paths,
                                                  key=os.path.getmtime))
    names = op_names or {}
    out = []
    for plane in data.planes:
        dev = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if not dev and not ev.name.startswith(HOST_PREFIXES):
                    continue
                scope = ""
                if dev and line.name == OPS_LINE:
                    scope = scope_of(names.get(hlo_head(ev.name), ""))
                out.append(Op(plane.name, line.name, ev.name,
                              float(ev.start_ns), float(ev.duration_ns),
                              scope))
    return out


def self_times(ops: Sequence[Op]) -> List[float]:
    """Each operation's duration less the union of the operations of the
    same line that start and end inside it (events on one line nest)."""
    order = sorted(range(len(ops)),
                   key=lambda i: (ops[i].start_ns, -ops[i].dur_ns))
    selft = [op.dur_ns for op in ops]
    stack: List[int] = []     # open operations, outermost first
    for i in order:
        op = ops[i]
        while stack and ops[stack[-1]].end_ns <= op.start_ns:
            stack.pop()
        if stack and op.end_ns <= ops[stack[-1]].end_ns:
            # a direct child: the parent loses its interval (children of
            # one parent do not overlap on one line)
            selft[stack[-1]] -= op.dur_ns
        stack.append(i)
    return selft


def _measure(iv: Iterable[Tuple[float, float]]) -> float:
    return sum(b - a for a, b in union(iv))


def _intersect(a: List[Tuple[float, float]],
               b: List[Tuple[float, float]]) -> float:
    """Length of the intersection of two merged, sorted interval lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def reduce(ops: Sequence[Op], *, window: str = WINDOW) -> Dict:
    """Scope self times, idle time split by engine work, and the host's
    accounting of the window (see the module docstring)."""
    host = [o for o in ops if not o.plane.startswith(DEVICE_PREFIX)]
    wins = [o for o in host if o.name == window]
    if not wins:
        raise ValueError(f"no {window!r} span in the trace")
    w0, w1 = wins[0].start_ns, wins[0].end_ns
    devices = sorted({o.plane for o in ops
                      if o.plane.startswith(DEVICE_PREFIX)})
    if not devices:
        raise ValueError(f"no device plane {DEVICE_PREFIX}* in the trace")

    def clip(o):
        a, b = max(o.start_ns, w0), min(o.end_ns, w1)
        return (a, b) if b > a else None

    scope_ns: Dict[str, Dict[str, float]] = {"prefill": {}, "decode": {},
                                             "other": {}}
    first_busy: List[Tuple[float, float]] = []
    for di, dev in enumerate(devices):
        mods = sorted((o for o in ops if o.plane == dev
                       and o.line == MODULES_LINE),
                      key=lambda o: o.start_ns)
        line = [o for o in ops if o.plane == dev and o.line == OPS_LINE]
        for op, st in zip(line, self_times(line)):
            c = clip(op)
            if c is None:
                continue
            mid = (op.start_ns + op.end_ns) / 2
            kind = next((classify(m.name) for m in mods
                         if m.start_ns <= mid <= m.end_ns), "other")
            s = op.scope or "unscoped"
            # self time, cut to the window in proportion
            ns = st * (c[1] - c[0]) / op.dur_ns if op.dur_ns else 0.0
            scope_ns[kind][s] = scope_ns[kind].get(s, 0.0) + ns
        if di == 0:
            first_busy = union(c for c in map(clip, line) if c)
    nd = len(devices)
    idle = []
    edges = [w0] + [x for ab in first_busy for x in ab] + [w1]
    for a, b in zip(edges[::2], edges[1::2]):
        if b > a:
            idle.append((a, b))
    steps = union(c for c in map(clip, (o for o in host
                                        if o.name == "serve.step")) if c)
    acc = [c for c in map(clip, (o for o in host if o.name in
                                 ("serve.step", "bench.idle",
                                  "bench.submit"))) if c]
    return {
        "window_s": (w1 - w0) / 1e9,
        "scope_s": {k: {s: v / nd / 1e9 for s, v in
                        sorted(d.items(), key=lambda kv: -kv[1])}
                    for k, d in scope_ns.items()},
        "idle_s": _measure(idle) / 1e9,
        "idle_with_work_s": _intersect(idle, steps) / 1e9,
        "accounted_s": _measure(acc) / 1e9,
        "serve_steps": sum(1 for o in host if o.name == "serve.step"
                           and w0 <= o.start_ns <= w1),
    }


def admit_waits(events: Sequence[Dict], requests: Sequence[Dict]
                ) -> Dict[int, float]:
    """Seconds from each request's scheduled arrival to its admission
    (the close of its ``request.queued`` span in the tracer's buffer).
    The run record stamps ``submit`` just after ``ServeEngine.submit``
    returns, and the span opens inside it: arrival to submit is read off
    the run record, submit to admission off the buffer."""
    opened: Dict[str, float] = {}
    closed: Dict[str, float] = {}
    for e in events:
        if e["name"] != "request.queued":
            continue
        if e["ph"] == "b":
            opened.setdefault(e["id"], e["ts"])
        elif e["ph"] == "e" and e["id"] in opened:
            closed.setdefault(e["id"], e["ts"])
    out = {}
    for r in requests:
        k = str(r["rid"])
        if r.get("submit") is not None and k in closed:
            out[r["rid"]] = (r["submit"] - r["arrival"]) \
                + (closed[k] - opened[k])
    return out


def setup_compile_s(events: Sequence[Dict]) -> float:
    """Seconds of ``compile.bucket`` and ``compile.exact`` spans that
    ended before the first request of the window (rid 0 or more) was
    submitted: the set-up's compiles."""
    first = min((e["ts"] for e in events if e["name"] == "request.queued"
                 and e["ph"] == "b" and int(e["id"]) >= 0),
                default=float("inf"))
    return sum(e["dur"] for e in events
               if e["name"] in ("compile.bucket", "compile.exact")
               and e["ph"] == "X" and e["dur"] is not None
               and e["ts"] + e["dur"] <= first)
