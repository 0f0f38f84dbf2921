"""Compile cache — keyed on (pattern fingerprint, bucket signature).

DISC §2: "these fusion engines will compile and generate kernel for every
emerging shape, even though some of them share the same computation
pattern" — the cache key here deliberately contains **no concrete shapes**,
only the shape-free graph fingerprint and the bucket signature, so compile
count is O(#buckets), not O(#shapes).

Also implements §4.4's static/dynamic mix: signatures that stay hot are
*escalated* to exact-shape static specializations (better codegen: no
masking, no padding waste), bounded by an LRU budget.

A compile is timed where it happens.  An ahead-of-time build compiles
inside ``compile_fn``.  A ``jax.jit`` wrapper (the ``jit`` pipeline)
traces, then compiles or loads from JAX's persistent cache, at its first
call: a compile function marked :func:`at_first_call` has its entry's
first call timed instead, and the bare wrapper takes the entry's place
after it.  The ``compile.bucket`` / ``compile.exact`` span,
``stats.compile_seconds`` and the timeline event cover the compile
either way, with JAX's own compile events inside it summed onto the
span.
"""
from __future__ import annotations

import time
from collections import OrderedDict
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Set, Tuple

from ..errors import (CONTROL_EXCEPTIONS, DEFAULT_RETRY, RetryPolicy,
                      wrap_compile_error)
from ..ft import faults
from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace

__all__ = ["CompileCache", "CacheStats", "at_first_call"]

# JAX's compile events (``jax.monitoring``): the seconds of each stage of
# obtaining an executable, by the span argument that sums them
_JAX_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    # an XLA compile, or a load from the persistent compilation cache
    "/jax/core/compile/backend_compile_duration": "backend_compile_s",
}
_JAX_CACHE_HIT = "/jax/compilation_cache/cache_hits"

# one accumulator per open compile span, innermost last
_open_compiles: List[Dict[str, Any]] = []
_listening = False


def _on_jax_duration(event: str, duration: float, **kw) -> None:
    if _open_compiles and event in _JAX_STAGES:
        _open_compiles[-1][_JAX_STAGES[event]] += duration


def _on_jax_event(event: str, **kw) -> None:
    if _open_compiles and event == _JAX_CACHE_HIT:
        _open_compiles[-1]["persistent_cache_hit"] = True


def _listen() -> None:
    """Register the JAX compile-event listeners, once per process, at the
    first compile (``jax.monitoring`` has no public unregister); outside
    a compile each event costs one empty-list test."""
    global _listening
    if not _listening:
        import jax

        jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)
        jax.monitoring.register_event_listener(_on_jax_event)
        _listening = True


def at_first_call(compile_fn: Callable) -> Callable:
    """Mark a compile function whose entry compiles at its first call (it
    returns a ``jax.jit`` wrapper): the cache times that call."""
    compile_fn.at_first_call = True
    return compile_fn


@dataclass
class CacheStats:
    hits: int = 0
    misses: int = 0
    # seconds spent obtaining executables: tracing, lowering, and the XLA
    # compile or the load from JAX's persistent cache
    compile_seconds: float = 0.0
    escalations: int = 0
    evictions: int = 0
    # promote-on-change re-lowerings: a call broke a dim tie inferred from
    # the first call, so the artifact was re-lowered with independent dims
    promotions: int = 0
    # fault plane: transient compile failures retried with backoff, and
    # §4.4 exact escalations whose compile failed permanently (the exact
    # sig is pinned to the padded bucket path thereafter)
    retries: int = 0
    escalation_failures: int = 0

    @property
    def compiles(self) -> int:
        return self.misses

    def as_dict(self) -> Dict[str, float]:
        return {
            "hits": self.hits,
            "misses": self.misses,
            "compiles": self.compiles,
            "compile_seconds": round(self.compile_seconds, 4),
            "escalations": self.escalations,
            "evictions": self.evictions,
            "promotions": self.promotions,
            "retries": self.retries,
            "escalation_failures": self.escalation_failures,
        }


class CompileCache:
    def __init__(self, fingerprint: str, max_entries: int = 256,
                 escalation_threshold: Optional[int] = None) -> None:
        self.fingerprint = fingerprint
        self.max_entries = max_entries
        self.escalation_threshold = escalation_threshold
        self._entries: "OrderedDict[Tuple, Any]" = OrderedDict()
        self._exact_hits: Dict[Tuple, int] = {}
        # exact signatures whose escalation compile failed permanently:
        # should_escalate() answers False for them forever after, so the
        # dispatch keeps serving the padded bucket artifact instead of
        # re-attempting a compile that cannot succeed on every call
        self._failed_exact: Set[Tuple] = set()
        self.retry_policy: RetryPolicy = DEFAULT_RETRY
        self.stats = CacheStats()
        obs_metrics.register_collector("compile", self._obs_collect,
                                       name=fingerprint)

    def _obs_collect(self) -> Dict[str, Any]:
        """Pull collector for ``disc.observe()["compile"]``."""
        return dict(self.stats.as_dict(), entries=len(self._entries))

    def _compile_with_retry(self, compile_fn: Callable[[], Any],
                            what: str, site: str) -> Any:
        """Run ``compile_fn`` under the taxonomy: raw errors are wrapped
        into :class:`~repro.errors.CompileError` (classified transient or
        permanent), transient failures retry with capped exponential
        backoff, and the named fault site fires first when an injector is
        installed."""
        attempt = 0
        while True:
            try:
                if faults.ACTIVE is not None:
                    faults.ACTIVE.check(site, key=what)
                return compile_fn()
            except CONTROL_EXCEPTIONS:
                raise
            except Exception as e:  # noqa: BLE001 — classified below
                err = wrap_compile_error(e, what)
                if not err.transient \
                        or attempt >= self.retry_policy.max_retries:
                    raise err from e
                self.stats.retries += 1
                time.sleep(self.retry_policy.delay(attempt))
                attempt += 1

    def _timed(self, kind: str, sig: Tuple, fp: str,
               run: Callable[[], Any]) -> Any:
        """Run one ``kind`` ("bucket" or "exact") compile under its span,
        its seconds counted in ``stats`` and journaled on the timeline."""
        _listen()
        acc: Dict[str, Any] = dict.fromkeys(_JAX_STAGES.values(), 0.0)
        acc["persistent_cache_hit"] = False
        sp = (obs_trace.ACTIVE.begin(f"compile.{kind}", cat="compile",
                                     key=str(sig),
                                     artifact=fp[:40], cache_hit=False)
              if obs_trace.ACTIVE is not None else None)
        _open_compiles.append(acc)
        t0 = time.perf_counter()
        try:
            out = run()
        finally:
            dt = time.perf_counter() - t0
            _open_compiles.remove(acc)
            self.stats.compile_seconds += dt
            if sp is not None:
                sp.end(**acc)
        obs_metrics.record_event(
            "compile.bucket" if kind == "bucket" else "escalate",
            key=str(sig), artifact=fp[:40], seconds=round(dt, 4))
        return out

    def _obtain(self, key: Tuple, kind: str, sig: Tuple, fp: str,
                compile_fn: Callable[[], Any]) -> Any:
        """The entry for a missed ``key``: compiled now, or, where
        ``compile_fn`` is marked :func:`at_first_call`, an entry whose
        first call is timed as the compile."""
        # the fault-site key carries the artifact fingerprint so an
        # injector can target one artifact (match="prefill") of a shared
        # cache
        what, site = f"{fp} {kind} {sig}", f"compile.{kind}"
        if not getattr(compile_fn, "at_first_call", False):
            return self._timed(kind, sig, fp, lambda: self._compile_with_retry(
                compile_fn, what, site))
        fn = self._compile_with_retry(compile_fn, what, site)

        def first_call(*args):
            out = self._timed(kind, sig, fp, lambda: fn(*args))
            if self._entries.get(key) is first_call:
                self._entries[key] = fn
            return out

        return first_call

    # --------------------------------------------------------- bucketed --
    def get_or_compile(self, bucket_sig: Tuple, compile_fn: Callable[[], Any],
                       fingerprint: Optional[str] = None) -> Any:
        """Look up / build the artifact for one bucket signature.

        ``fingerprint`` overrides the cache's default graph fingerprint so a
        single cache instance can be shared by several compiled artifacts
        (e.g. a serving engine's prefill + decode functions) — entries never
        collide because the fingerprint is part of the key.
        """
        key = ("bucket", fingerprint or self.fingerprint, bucket_sig)
        entry = self._entries.get(key)
        if entry is not None:
            self.stats.hits += 1
            self._entries.move_to_end(key)
            return entry
        self.stats.misses += 1
        entry = self._obtain(key, "bucket", bucket_sig,
                             fingerprint or self.fingerprint, compile_fn)
        self._entries[key] = entry
        self._evict()
        return entry

    # ------------------------------------------------- static escalation --
    def should_escalate(self, exact_sig: Tuple,
                        fingerprint: Optional[str] = None,
                        threshold: Optional[int] = None) -> bool:
        """§4.4: route hot exact shapes to the static compiler."""
        threshold = self.escalation_threshold if threshold is None else threshold
        if threshold is None:
            return False
        key = (fingerprint or self.fingerprint, exact_sig)
        if key in self._failed_exact:
            return False
        n = self._exact_hits.get(key, 0) + 1
        self._exact_hits[key] = n
        return n >= threshold

    def note_escalation_failure(self, exact_sig: Tuple,
                                fingerprint: Optional[str] = None) -> None:
        """Record a permanently failed §4.4 escalation compile: the exact
        signature is pinned to the padded bucket path (``should_escalate``
        answers False for it from now on)."""
        self._failed_exact.add((fingerprint or self.fingerprint, exact_sig))
        self.stats.escalation_failures += 1
        obs_metrics.record_event(
            "escalate.fail", key=str(exact_sig),
            artifact=(fingerprint or self.fingerprint)[:40])

    def get_or_compile_exact(self, exact_sig: Tuple,
                             compile_fn: Callable[[], Any],
                             fingerprint: Optional[str] = None) -> Any:
        key = ("exact", fingerprint or self.fingerprint, exact_sig)
        entry = self._entries.get(key)
        if entry is not None:
            self.stats.hits += 1
            self._entries.move_to_end(key)
            return entry
        self.stats.misses += 1
        self.stats.escalations += 1
        entry = self._obtain(key, "exact", exact_sig,
                             fingerprint or self.fingerprint, compile_fn)
        self._entries[key] = entry
        self._evict()
        return entry

    def drop_fingerprint(self, fingerprint: str) -> int:
        """Drop every entry (and escalation counter) keyed under
        ``fingerprint``.

        Used by promote-on-change: after a re-lower the old artifact's
        entries are unreachable (its fingerprint is never asked for
        again) but would otherwise pin compiled executables in the LRU
        until enough newer entries forced them out.  Returns the number
        of entries dropped.
        """
        dead = [k for k in self._entries if k[1] == fingerprint]
        for k in dead:
            del self._entries[k]
        self._exact_hits = {k: v for k, v in self._exact_hits.items()
                            if k[0] != fingerprint}
        self._failed_exact = {k for k in self._failed_exact
                              if k[0] != fingerprint}
        return len(dead)

    def _evict(self) -> None:
        while len(self._entries) > self.max_entries:
            self._entries.popitem(last=False)
            self.stats.evictions += 1

    def __len__(self) -> int:
        return len(self._entries)
