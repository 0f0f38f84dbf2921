"""The serving engine as the benchmark drives it: spans and launch records.

:class:`BenchEngine` is ``ServeEngine`` with nothing changed but what it
records. Around generator submit, admission, each prefill group, each
decode step and each launch it opens a host span (a
``jax.profiler.TraceAnnotation`` when the run is traced, so that the
trace names what the host did while the device idled). It keeps, per
prefill group, the host wall from the gather of its rows to the return
of its logits and first tokens, with the true prompt lengths launched;
and per decode step, the host wall and the cache fill of each active
row before the step.
"""
from __future__ import annotations

import contextlib
import time
from typing import Callable, List, Tuple

import jax
import numpy as np

from repro.api import ServeEngine

__all__ = ["BenchEngine", "no_span", "trace_span"]


def no_span(name: str):
    return contextlib.nullcontext()


def trace_span(name: str):
    return jax.profiler.TraceAnnotation(name)


class BenchEngine(ServeEngine):
    """``ServeEngine`` plus host spans and per-launch records.

    ``prefills``: ``(start, end, lens)`` per prefill group, ``lens`` the
    true prompt lengths of the launch. ``decodes``: ``(start, end,
    fills)`` per decode step, ``fills`` the cache fill of each active
    row. Times are ``time.perf_counter()`` readings."""

    def __init__(self, *args, span: Callable = no_span, **kw):
        self._span = span
        self.prefills: List[Tuple[float, float, List[int]]] = []
        self.decodes: List[Tuple[float, float, List[int]]] = []
        self._launched: List[int] = []
        super().__init__(*args, **kw)

    def submit(self, reqs) -> None:
        with self._span("bench.submit"):
            super().submit(reqs)

    def _admit(self) -> None:
        with self._span("bench.admit"):
            super()._admit()

    def _launch(self, kind: str, fn, *args):
        if kind == "prefill":
            # (params, rows, tokens, lens, offsets)
            self._launched = [int(n) for n in np.asarray(args[3])]
        elif kind == "decode":
            self._launched = [int(self.lens[i])
                              for i, s in enumerate(self.slots)
                              if s is not None and s.state == "decode"]
        with self._span(f"bench.launch.{kind}"):
            return super()._launch(kind, fn, *args)

    def _prefill_group(self) -> None:
        self._launched = []
        t0 = time.perf_counter()
        with self._span("bench.prefill"):
            super()._prefill_group()
        if self._launched:
            self.prefills.append((t0, time.perf_counter(), self._launched))

    def _decode(self) -> None:
        self._launched = []
        t0 = time.perf_counter()
        with self._span("bench.decode"):
            super()._decode()
        if self._launched:
            self.decodes.append((t0, time.perf_counter(), self._launched))
