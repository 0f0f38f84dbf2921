"""The staged public pipeline: ``disc.compile(fn) → lower() → compile()``.

Mirrors JAX's AOT staging (``jit(f).lower(...).compile()``) for the whole
DISC compiler:

* :func:`compile` returns a :class:`CompiledFunction` — callable
  immediately (lowering/compiling happens on demand, with spec inference
  from the first call when no specs were given), and stageable explicitly;
* :class:`Lowered` holds the inspectable compile-time artifacts (DHLO
  graph, fusion / placement / buffer plans, dynamic symbols) before any
  device code exists;
* :class:`Compiled` owns the generated host dispatcher plus the per-bucket
  compile cache, and exposes ``dispatch_source`` / ``cache_stats()`` /
  ``compile_counts()`` for introspection.

Two pipelines share this surface (selected by
``CompileOptions.pipeline``):

* ``"dhlo"`` — the paper's full pipeline: jaxpr → DHLO bridge, shape
  constraints, fusion, placement, buffers, bucketed per-backend codegen,
  generated host dispatch with output recovery.
* ``"jit"``  — bucketed dispatch over a jax-traceable function *without*
  bridging it through DHLO: declared dynamic args are bucket-padded and
  one ``jax.jit`` entry is cached per bucket signature.  Pytree args pass
  through untouched (spec ``None``), so whole models (params/KV-cache
  trees) get the O(#buckets) compile contract — this is what the serving
  engine builds prefill/decode on.

Both pipelines share one host-dispatch emitter
(:func:`repro.core.dispatcher.generate_dispatch`), parameterized by a
``DispatchLens`` — so §4.4 static escalation (hot exact signatures get an
unpadded specialization) and the tie guards behind promote-on-change work
identically under either.
"""
from __future__ import annotations

import hashlib
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from ..core.bucketing import BucketPolicy
from ..core.cache import CompileCache, at_first_call
from ..errors import CONTROL_EXCEPTIONS, CompileError, classify_transient
from ..core.codegen import dyn_symbols
from ..core.dispatcher import dhlo_lens, generate_dispatch, jit_lens
from ..core.symshape import SymDim
from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace
from ..frontends.jaxpr_frontend import ArgSpec, TreeSpec, bridge
from .backends import get_backend
from .options import CompileOptions, Dim, normalize_specs

__all__ = ["compile", "CompiledFunction", "Lowered", "Compiled"]


# ------------------------------------------------------------- inference --

def infer_specs(arrays: Sequence[Any]) -> List[ArgSpec]:
    """Infer ``ArgSpec``s from one call's concrete arguments.

    Every axis of size > 1 becomes a symbolic dim; axes sharing a size in
    this call share a symbol (so contractions stay well-typed when traced
    at representative sizes).  Size-1 axes stay static (broadcasting).
    The inferred profile is exact for any later call with the same
    equality structure; distinct dims that *happened* to coincide on the
    first call are tied — declare specs explicitly to untie them.
    """
    by_size: Dict[int, str] = {}
    specs: List[ArgSpec] = []
    for a in arrays:
        ashape = np.shape(a)
        dtype = getattr(a, "dtype", None)
        if dtype is None:
            dtype = np.asarray(a).dtype
        shape = []
        for size in ashape:
            if size <= 1:
                shape.append(int(size))
            else:
                shape.append(by_size.setdefault(int(size), f"d{size}"))
        specs.append(ArgSpec(tuple(shape), dtype))
    return specs


def _graph_const_token(graph) -> str:
    """Hash of a DHLO graph's literal payloads, in deterministic order.

    Recurses into region ops' nested body graphs (attrs holding a
    ``DGraph`` or a tuple of them) — a region's closure constants are as
    cache-relevant as top-level literals.
    """
    from ..core.dhlo import DGraph

    h = hashlib.sha1()
    seen = set()

    def walk(g) -> None:
        for op in g.ops:
            for v in list(op.inputs) + list(op.shape_operands):
                if v.literal is not None and v.vid not in seen:
                    seen.add(v.vid)
                    arr = np.asarray(v.literal)
                    h.update(str(arr.dtype).encode())
                    h.update(repr(arr.shape).encode())
                    h.update(arr.tobytes())
            for av in op.attrs.values():
                if isinstance(av, DGraph):
                    walk(av)
                elif isinstance(av, (tuple, list)):
                    for x in av:
                        if isinstance(x, DGraph):
                            walk(x)

    walk(graph)
    return h.hexdigest()[:16]


def _fn_token(fn: Callable) -> str:
    """An identity token for ``fn`` (code, closure, bound instance).

    Process-local: bound methods are distinguished by instance identity
    (two engines sharing one cache must never serve each other's
    closures), so tokens are not stable across processes — fine for an
    in-memory compile cache.
    """
    parts: List[str] = []
    base = getattr(fn, "__func__", fn)
    self_obj = getattr(fn, "__self__", None)
    if self_obj is not None:
        parts.append(type(self_obj).__qualname__)
        parts.append(str(id(self_obj)))
    code = getattr(base, "__code__", None)
    if code is None:
        parts.append(repr(base))
    else:
        parts.append(getattr(base, "__qualname__", ""))
        parts.append(hashlib.sha1(code.co_code).hexdigest())
        parts.append(repr(code.co_consts)[:2000])
        for cell in base.__closure__ or ():
            try:
                parts.append(repr(cell.cell_contents)[:200])
            except ValueError:  # empty cell
                parts.append("<empty>")
    return "\x00".join(parts)


# --------------------------------------------------------------- lowered --

@dataclass
class Lowered:
    """Compile-time artifacts of one function at one spec signature.

    For the ``"dhlo"`` pipeline all plan fields are populated; for the
    ``"jit"`` pipeline only ``specs`` / ``sym_names`` are (there is no hub
    IR — the function is staged directly through ``jax.jit`` per bucket).
    """

    fn: Callable
    specs: Tuple[Optional[ArgSpec], ...]
    options: CompileOptions
    policy: BucketPolicy
    pipeline: str
    graph: Any = None
    plan: Any = None              # FusionPlan
    placement: Any = None
    buffer_plan: Any = None
    syms: Tuple[SymDim, ...] = ()
    sym_names: Tuple[str, ...] = ()
    # SPMD ShardingPlan when lowered under CompileOptions(mesh=...);
    # ``policy`` is then the planner-tightened policy (sharded dynamic
    # dims' buckets are mesh-axis multiples)
    sharding_plan: Any = None

    def _spmd_token(self) -> str:
        """Distinguish same-pattern artifacts lowered for different
        meshes/profiles: their bucket entries are compiled against
        different shardings and must never share cache entries.  Device
        identity is part of the token — two same-shape meshes over
        different device sets produce incompatible executables."""
        if self.sharding_plan is None:
            return ""
        device_ids = tuple(
            d.id for d in self.sharding_plan.mesh.devices.flat)
        h = hashlib.sha1((repr(self.sharding_plan.report())
                          + repr(device_ids)).encode())
        return "+spmd:" + h.hexdigest()[:12]

    def fingerprint(self) -> str:
        if self.graph is not None:
            # DGraph.fingerprint() is deliberately shape-free AND
            # constant-free (the per-engine cache-key property).  As a
            # *shared*-cache key that is too weak: two graphs with the same
            # wiring but different literal payloads must not collide, so
            # the artifact fingerprint folds the constants in (and the
            # SPMD plan, when lowered under a mesh).
            return (self.graph.fingerprint() + "+"
                    + _graph_const_token(self.graph) + self._spmd_token())
        # jit pipeline has no shape-free graph fingerprint; identify the
        # artifact by the *function* (code + closure + bound self) plus the
        # spec signature, so distinct functions sharing one CompileCache
        # can never hit each other's entries
        def _sig(s):
            if s is None:
                return None
            if isinstance(s, TreeSpec):
                return ("tree", s.axes)
            return (s.shape, str(np.dtype(s.dtype)))

        sig = repr([_sig(s) for s in self.specs])
        h = hashlib.sha1((sig + "\x00" + _fn_token(self.fn)).encode())
        return (f"jit:{self.options.name}:{h.hexdigest()[:16]}"
                + self._spmd_token())

    def compile(self, options: Optional[CompileOptions] = None, *,
                on_tie_break: Optional[Callable] = None) -> "Compiled":
        """Build the dispatcher (device code still compiles per bucket,
        lazily, through the backend registry).

        ``options`` may override backend / cache / escalation at this
        stage; the bucketing policy is part of the lowering contract
        (``Dim`` markers were folded into it) and stays fixed.
        ``on_tie_break`` handles a call that breaks a multi-site symbol
        tie (:class:`CompiledFunction` wires promote-on-change through
        it); without a handler such a call raises a contract error.
        """
        return Compiled(self, options or self.options,
                        on_tie_break=on_tie_break)

    def as_text(self) -> str:
        """Human-readable summary of the lowering (inspectable stage)."""
        lines = [f"Lowered({self.options.name!r}, pipeline={self.pipeline!r})"]
        lines.append(f"  fingerprint: {self.fingerprint()}")
        lines.append(f"  dynamic symbols: {list(self.sym_names)}")
        if self.graph is not None:
            lines.append(f"  params: {len(self.graph.params)}  "
                         f"ops: {len(self.graph.ops)}  "
                         f"outputs: {len(self.graph.outputs)}")
            lines.append(f"  fusion: {self.plan.stats()}")
            lines.append(f"  placement: {self.placement.report()}")
            lines.append(f"  constraints: {self.graph.store.stats()}")
        else:
            lines.append("  (no DHLO graph: jit pipeline stages the "
                         "function directly per bucket)")
        return "\n".join(lines)


def _lower(fn: Callable, specs: Sequence[Optional[ArgSpec]],
           dims: Sequence[Dim], options: CompileOptions) -> Lowered:
    sp = (obs_trace.ACTIVE.begin("lower", cat="compile",
                                 artifact=options.name,
                                 pipeline=options.pipeline)
          if obs_trace.ACTIVE is not None else None)
    try:
        return _lower_impl(fn, specs, dims, options)
    finally:
        if sp is not None:
            sp.end()


def _lower_impl(fn: Callable, specs: Sequence[Optional[ArgSpec]],
                dims: Sequence[Dim], options: CompileOptions) -> Lowered:
    policy = options.policy_with_dims(dims)
    sharding_plan = None
    if options.mesh is not None:
        # SPMD planning happens at lower() time: per-arg shardings are
        # derived from the profile and the policy is tightened so every
        # sharded dynamic dim's bucket divides the mesh axes evenly
        # (ConstraintViolation here when the Dim contract cannot comply)
        from ..dist.profiles import get_profile
        from ..dist.spmd import plan_spmd
        profile = get_profile(options.sharding_profile or "dp")
        sharding_plan, policy = plan_spmd(specs, policy, options.mesh,
                                          profile)
    if options.pipeline == "jit":
        sym_names: List[str] = []
        for s in specs:
            if s is None:
                continue
            names = ([d for _, d in s.axes] if isinstance(s, TreeSpec)
                     else [d for d in s.shape if isinstance(d, str)])
            for d in names:
                if d not in sym_names:
                    sym_names.append(d)
        return Lowered(fn=fn, specs=tuple(specs), options=options,
                       policy=policy, pipeline="jit",
                       sym_names=tuple(sym_names),
                       sharding_plan=sharding_plan)

    if any(not isinstance(s, ArgSpec) for s in specs):
        raise ValueError(
            "the 'dhlo' pipeline needs an ArgSpec for every argument "
            "(None pass-through and TreeSpec pytree specs are only "
            "supported by CompileOptions(pipeline='jit'))")
    from ..core.fusion import plan_fusion
    from ..core.placer import place
    from ..core.buffers import plan_buffers

    graph, _ = bridge(fn, list(specs), name=options.name,
                      bounds={d.name: d.max for d in dims
                              if d.max is not None})
    plan = plan_fusion(graph)
    placement = place(graph, mesh=options.mesh)
    # bucket-generic symbolic memory plan, decided ONCE here — every
    # bucket entry, the VM, and donate_argnums realize the same plan
    buffer_plan = plan_buffers(graph, policy,
                               symbolic=options.memory_planning,
                               donation=options.plan_donation)
    buffer_plan.lines_text = buffer_plan.render_lines(graph)
    graph.memory_plan = buffer_plan
    syms = tuple(dyn_symbols(graph))
    if sharding_plan is not None:
        # surface the plan-time divisibility facts in the constraint
        # store (report()["constraints"]["mesh_constraints"])
        for c in sharding_plan.constraints:
            graph.store.note_mesh_divisible(c.dim, c.axes, c.multiple_of)
    return Lowered(fn=fn, specs=tuple(specs), options=options,
                   policy=policy, pipeline="dhlo", graph=graph, plan=plan,
                   placement=placement, buffer_plan=buffer_plan, syms=syms,
                   sym_names=tuple(s.name for s in syms),
                   sharding_plan=sharding_plan)


# -------------------------------------------------------------- compiled --

class Compiled:
    """The executable artifact: generated host dispatch + compile cache.

    Both pipelines flow through the one emitter in
    :mod:`repro.core.dispatcher`; all that differs is the
    :class:`~repro.core.dispatcher.DispatchLens` (how sizes are observed,
    what gets padded, whether outputs are recovered) and the per-bucket /
    exact compile callbacks (backend registry vs ``jax.jit``).  That means
    the jit pipeline gets the §4.4 static-escalation branch and the tie
    guards for free.
    """

    def __init__(self, lowered: Lowered, options: CompileOptions,
                 on_tie_break: Optional[Callable] = None) -> None:
        self.lowered = lowered
        self.options = options
        self.backend = get_backend(options.backend)
        self._fingerprint = lowered.fingerprint()
        self.cache = options.cache if options.cache is not None else \
            CompileCache(self._fingerprint,
                         max_entries=options.max_cache_entries,
                         escalation_threshold=options.escalation_threshold)
        self._bucket_compiles = 0
        self._exact_compiles = 0
        if lowered.pipeline == "dhlo":
            lens = dhlo_lens(lowered.graph, lowered.syms)
            compile_bucket = self._compile_bucket
            compile_exact = self._compile_exact
        else:
            lens = jit_lens(lowered.specs, lowered.sym_names,
                            name=options.name)
            compile_bucket = self._compile_jit_bucket
            compile_exact = self._compile_jit_exact
        self._dispatch, self.dispatch_source = generate_dispatch(
            lens, lowered.policy, self.cache, compile_bucket, compile_exact,
            fingerprint=self._fingerprint,
            escalation_threshold=options.escalation_threshold,
            on_tie_break=on_tie_break,
            sharding=lowered.sharding_plan,
            memory_plan=lowered.buffer_plan)
        self._mstats = self._dispatch._mstats
        obs_metrics.register_collector("dispatch", self._obs_dispatch,
                                       name=options.name)
        obs_metrics.register_collector("memory", self._obs_memory,
                                       name=options.name)

    def _obs_dispatch(self) -> Dict[str, Any]:
        """Pull collector: ``disc.observe()["dispatch"][name]``."""
        return self._mstats.cost_dict()

    def _obs_memory(self) -> Dict[str, Any]:
        """Pull collector: ``disc.observe()["memory"][name]`` (the light
        staging view; ``memory_report()`` has the full per-bucket plan)."""
        return dict(self._mstats.as_dict(),
                    planning=bool(self.options.memory_planning
                                  and self.lowered.pipeline == "dhlo"))

    # ------------------------------------------------------------ public --
    def __call__(self, *arrays):
        outs = self._dispatch(arrays)
        if self.lowered.pipeline == "jit":
            return outs
        return outs[0] if len(outs) == 1 else tuple(outs)

    @property
    def graph(self):
        return self.lowered.graph

    @property
    def plan(self):
        return self.lowered.plan

    @property
    def placement(self):
        return self.lowered.placement

    @property
    def buffer_plan(self):
        return self.lowered.buffer_plan

    @property
    def syms(self):
        return list(self.lowered.syms)

    @property
    def policy(self) -> BucketPolicy:
        return self.lowered.policy

    @property
    def n_compiles(self) -> int:
        return self._bucket_compiles + self._exact_compiles

    def cache_stats(self) -> Dict[str, float]:
        return self.cache.stats.as_dict()

    def cost_report(self) -> Dict[str, Any]:
        """Dynamic-shape cost accounting for this artifact: per-bucket
        hit histogram, padding-waste ratio (padded vs true bytes per
        launch), and the host-dispatch vs entry-call wall split."""
        return self._mstats.cost_dict()

    def bucket_programs(self) -> Dict[Tuple[int, ...], Any]:
        """This artifact's cached bucket entries, keyed by bucket
        signature — under the AOT backends each is a ``jax.stages.Compiled``
        whose ``as_text()`` is the program the device runs."""
        return {k[2]: entry for k, entry in list(self.cache._entries.items())
                if len(k) == 3 and k[0] == "bucket"
                and k[1] == self._fingerprint}

    def compile_counts(self) -> Dict[str, int]:
        """Per-artifact compile counts (meaningful under shared caches)."""
        return {"bucket": self._bucket_compiles,
                "exact": self._exact_compiles,
                "total": self._bucket_compiles + self._exact_compiles}

    def report(self) -> Dict[str, Any]:
        rep: Dict[str, Any] = {
            "fingerprint": self._fingerprint,
            "backend": self.backend.name,
            "pipeline": self.lowered.pipeline,
            "cache": self.cache_stats(),
            "compiles": self.compile_counts(),
            "dynamic_symbols": list(self.lowered.sym_names),
        }
        low = self.lowered
        if low.sharding_plan is not None:
            # emitted per-arg shardings + mesh-divisibility constraints
            rep["sharding"] = low.sharding_plan.report()
        if low.graph is not None:
            templates = low.plan.template_counts()
            covered = sum(n for t, n in templates.items()
                          if t in self.backend.cluster_kernels) \
                if self.backend.cluster_kernels else 0
            rep.update({
                "fusion": low.plan.stats(),
                "placement": low.placement.report(),
                "constraints": low.graph.store.stats(),
                # clusters eligible for a fused-kernel template (plan
                # property) vs covered by THIS backend's registrations
                "pallas_eligible_clusters": sum(templates.values()),
                "cluster_templates": templates,
                "backend_covered_clusters": covered,
            })
        rep["memory"] = self.memory_report()
        rep["dispatch_cost"] = self.cost_report()
        return rep

    def memory_report(self) -> Dict[str, Any]:
        """The ``report()["memory"]`` section: the bucket-generic plan
        (symbolic peaks + reuse counts), concrete per-bucket peaks for
        every bucket this artifact has compiled, and the dispatch's
        staging-buffer accounting.  Documented in ``docs/api.md``."""
        low = self.lowered
        mem: Dict[str, Any] = {
            "planning": bool(self.options.memory_planning
                             and low.pipeline == "dhlo"),
            "staging": self._mstats.as_dict(),
        }
        plan = low.buffer_plan
        if plan is None:
            return mem
        mem.update({
            "values": plan.n_values,
            "slots": plan.n_slots,
            "reuse_counts": dict(plan.reuse_counts),
            "donatable_args": list(plan.donatable_args),
            "symbolic_peak": plan.symbolic_peak(),
            "symbolic_peak_no_reuse": plan.symbolic_peak_no_reuse(),
        })
        per_bucket: Dict[str, Any] = {}
        for key in self.bucket_programs():
            bindings = {s.uid: int(v) for s, v in zip(low.syms, key)}
            peaks = plan.concrete_peaks(low.graph, bindings)
            reduction = (peaks["no_reuse_bytes"] / peaks["arena_bytes"]
                         if peaks["arena_bytes"] else 1.0)
            per_bucket[str(tuple(key))] = {
                **peaks, "reduction": round(reduction, 3)}
        mem["per_bucket"] = per_bucket
        return mem

    # ------------------------------------------------- device compilation --
    def _maybe_demote_backend(self) -> None:
        """Degradation ladder, backend rung: when this backend's cluster
        kernels have accumulated ``backend_demotion_strikes`` failed runs
        between them, new bucket/exact compiles build through the
        fallback backend (``xla``) instead — already-compiled entries
        keep serving (their clusters already fell back per-op)."""
        strikes_cap = self.options.backend_demotion_strikes
        kernels = self.backend.cluster_kernels
        if (strikes_cap is None or not kernels
                or self.backend.name == self.options.fallback_backend):
            return
        total = sum(k.strikes for k in kernels.values())
        if total >= strikes_cap:
            from ..core.codegen import KERNEL_DEMOTIONS
            KERNEL_DEMOTIONS.append(
                f"backend:{self.backend.name}->"
                f"{self.options.fallback_backend} after {total} strikes")
            obs_metrics.record_event(
                "backend.demote", artifact=self.options.name,
                backend=self.backend.name,
                fallback=self.options.fallback_backend, strikes=total)
            self.backend = get_backend(self.options.fallback_backend)

    def _compile_bucket(self, key: Tuple[int, ...]):
        self._maybe_demote_backend()
        low = self.lowered
        padded = {s.uid: int(k) for s, k in zip(low.syms, key)}
        self._bucket_compiles += 1
        donate = self.options.donate
        if donate and self.options.plan_donation and low.buffer_plan is not None:
            # realize the plan: donate exactly the params it proved dead
            # before the graph ends (never an aliased output / live arg)
            donate = low.buffer_plan.donatable_args
        if low.sharding_plan is not None:
            import inspect

            # AOT entries must compile against the exact input shardings
            # the generated dispatch device_puts: (lens, *args)
            shardings = (low.sharding_plan.lens_sharding(),
                         *(low.sharding_plan.arg_sharding(i)
                           for i in range(len(low.specs))))
            params = inspect.signature(self.backend.build_bucket).parameters
            if "arg_shardings" not in params and not any(
                    p.kind is inspect.Parameter.VAR_KEYWORD
                    for p in params.values()):
                # failing loudly here beats the far-away input-sharding
                # mismatch the AOT entry would raise at first call (the
                # generated dispatch device_puts inputs onto the mesh)
                raise ValueError(
                    f"backend {self.backend.name!r} cannot compile under "
                    f"CompileOptions(mesh=...): its build_bucket accepts "
                    f"no 'arg_shardings' keyword — add the parameter "
                    f"(see repro.api.backends) or compile without a mesh")
            return self.backend.build_bucket(
                low.graph, low.plan, low.syms, padded,
                donate, arg_shardings=shardings)
        return self.backend.build_bucket(low.graph, low.plan, low.syms,
                                         padded, donate)

    def _compile_exact(self):
        # a fresh executor per escalated signature (each cache entry is
        # hit by exactly one exact shape): if the LRU evicts the entry —
        # or promote-on-change purges it — its compiled executable is
        # actually freed, instead of living on inside a shared wrapper's
        # trace cache
        self._maybe_demote_backend()
        self._exact_compiles += 1
        return self.backend.build_exact(self.lowered.graph,
                                        self.lowered.plan)

    # ----------------------------------------------------- jit pipeline --
    @at_first_call
    def _compile_jit_bucket(self, key: Tuple[int, ...]):
        """One ``jax.jit`` entry per bucket signature: the dispatch pads
        dynamic args to the bucket, so the entry traces exactly once, at
        its first call (which the cache times as the compile)."""
        self._bucket_compiles += 1
        return jax.jit(self.lowered.fn)

    @at_first_call
    def _compile_jit_exact(self):
        """§4.4 for the jit pipeline: the escalated path calls the
        function at *unpadded* shapes, so hot shapes get a mask/padding-
        free compile.  One fresh ``jax.jit`` wrapper per escalated
        signature: the cache's LRU budget then genuinely bounds escalated
        executables (a single shared wrapper would retain every trace in
        its own cache, immune to eviction)."""
        self._exact_compiles += 1
        return jax.jit(self.lowered.fn)


# ------------------------------------------------------ public entrypoint --

def _split_tied_specs(specs: Sequence[Optional[ArgSpec]],
                      arrays: Sequence[Any]) -> Tuple[Optional[ArgSpec], ...]:
    """Refine an inferred spec profile against one call's observed sizes.

    Symbols whose sites no longer agree are split: each subgroup of sites
    that share a size in *this* call gets its own symbol (the subgroup
    containing the extraction site keeps the original name).  Sites that
    still coincide stay tied — the profile refines monotonically, one
    broken coincidence at a time, instead of over-constraining forever.
    """
    sizes: Dict[Tuple[int, int], int] = {}
    groups: Dict[str, List[Tuple[int, int]]] = {}
    for ai, spec in enumerate(specs):
        if spec is None:
            continue
        shape = np.shape(arrays[ai])
        for ax, d in enumerate(spec.shape):
            if isinstance(d, str):
                sizes[(ai, ax)] = int(shape[ax])
                groups.setdefault(d, []).append((ai, ax))

    used = set(groups)
    renames: Dict[Tuple[int, int], str] = {}
    for name, sites in groups.items():
        by_size: Dict[int, List[Tuple[int, int]]] = {}
        for site in sites:
            by_size.setdefault(sizes[site], []).append(site)
        if len(by_size) == 1:
            continue  # this tie survived the call
        keep = sizes[sites[0]]  # extraction-site subgroup keeps the name
        for size, subsites in by_size.items():
            if size == keep:
                continue
            new = f"{name}_{size}"
            while new in used:
                new += "_"
            used.add(new)
            for site in subsites:
                renames[site] = new

    out: List[Optional[ArgSpec]] = []
    for ai, spec in enumerate(specs):
        if spec is None:
            out.append(None)
            continue
        shape = tuple(renames.get((ai, ax), d)
                      for ax, d in enumerate(spec.shape))
        out.append(ArgSpec(shape, spec.dtype, spec.name))
    return tuple(out)


class CompiledFunction:
    """What ``disc.compile`` returns: callable now, stageable explicitly.

    * with specs: lowering + dispatcher generation happen eagerly (device
      code still compiles per bucket on demand);
    * without specs: the first call infers them (:func:`infer_specs`), and
      the inferred profile *refines itself*: dims that merely coincided on
      the first call are re-lowered as independent dims the moment a later
      call breaks the coincidence (promote-on-change — disable with
      ``CompileOptions(promote_on_change=False)``).

    Attribute access falls through to the underlying :class:`Compiled`
    artifact (``plan``, ``report()``, ``n_compiles``, ...), so migrating
    from ``DiscEngine`` is a constructor swap.
    """

    def __init__(self, fn: Callable,
                 specs: Optional[Sequence[Any]] = None,
                 options: Optional[CompileOptions] = None, **kw) -> None:
        if options is None:
            options = CompileOptions(**kw)
        elif kw:
            options = options.replace(**kw)
        self.fn = fn
        self.options = options
        self._specs, self._dims = normalize_specs(specs)
        self._inferred = False
        self._lowered: Optional[Lowered] = None
        self._compiled: Optional[Compiled] = None
        if self._specs is not None:
            self._ensure()

    # ------------------------------------------------------------ staging --
    def lower(self, specs: Optional[Sequence[Any]] = None) -> Lowered:
        """Stage 1: produce the inspectable compile-time artifacts."""
        if specs is not None:
            norm, dims = normalize_specs(specs)
            return _lower(self.fn, norm, dims, self.options)
        if self._specs is None:
            raise ValueError(
                "no specs declared and none inferred yet — pass specs to "
                "lower(), declare them in disc.compile(fn, specs), or call "
                "the function once to infer them")
        if self._lowered is None:
            self._lowered = _lower(self.fn, self._specs, self._dims,
                                   self.options)
        return self._lowered

    def _ensure(self) -> Compiled:
        if self._compiled is None:
            handler = self._promote if (
                self._inferred and self.options.promote_on_change) else None
            self._compiled = self.lower().compile(on_tie_break=handler)
        return self._compiled

    def _promote(self, arrays):
        """Promote-on-change: a call broke a dim tie the first-call
        inference assumed, so split the tied symbols by the observed sizes
        and re-lower.  The compile cache carries over (stats continuity;
        the refined artifact's keys carry strictly more symbols, so they
        can never collide with the superseded artifact's — even under the
        dhlo pipeline, whose shape-free graph fingerprint is *unchanged*
        by the re-lower) and the superseded entries are purged."""
        split = _split_tied_specs(self._specs, arrays)
        if split == self._specs:
            # a stale handle to a *superseded* artifact fired its guard,
            # but the live profile already accommodates this call (its
            # tied groups all agree on these sizes) — redispatch through
            # the live artifact instead of re-lowering a third one
            return self._ensure()._dispatch(arrays)
        snapshot = (self._specs, self.options, self._lowered, self._compiled)
        prev = self._compiled
        self._specs = split
        self.options = self.options.replace(cache=prev.cache)
        self._lowered = None
        self._compiled = None
        try:
            compiled = self._ensure()
        except CONTROL_EXCEPTIONS:
            # never swallow control flow — but still roll back so the
            # pre-promotion artifact survives an interrupt mid-re-lower
            self._specs, self.options, self._lowered, self._compiled = \
                snapshot
            raise
        except Exception as e:
            # roll back: the pre-promotion artifact stays valid for calls
            # that respect the original ties.  Classify before wrapping
            # (a transient backend OOM mid-re-lower is retryable; a
            # genuine equality requirement is not) and chain the original
            # error class into the raised CompileError.
            self._specs, self.options, self._lowered, self._compiled = \
                snapshot
            raise CompileError(
                f"promote-on-change failed for {self.options.name!r}: a "
                f"call broke a dim tie inferred from the first call, but "
                f"re-lowering with independent dims "
                f"{[s.shape for s in split if s is not None]} did not "
                f"succeed — the function itself may require the equality "
                f"({type(e).__name__}: {e})",
                transient=classify_transient(e)) from e
        prev.cache.stats.promotions += 1
        obs_metrics.record_event(
            "promote", artifact=self.options.name,
            symbols=list(compiled.lowered.sym_names))
        # the superseded artifact's entries are unreachable — free the
        # executables they pin.  This must happen before the refined
        # artifact compiles its first bucket: under the dhlo pipeline the
        # two artifacts share a (shape-free) fingerprint, and the refined
        # artifact has compiled nothing yet, so everything under the old
        # fingerprint is the old artifact's.
        prev.cache.drop_fingerprint(prev._fingerprint)
        # hand the triggering call to the refined artifact's dispatch (the
        # raw dispatch-level result: the caller is the *old* artifact's
        # generated flow, whose __call__ wrapper still post-processes it)
        return compiled._dispatch(arrays)

    # ------------------------------------------------------------ calling --
    def __call__(self, *arrays):
        if self._compiled is None:
            if self._specs is None:
                if self.options.pipeline == "jit":
                    # no declared dynamic dims: every arg passes through
                    self._specs = (None,) * len(arrays)
                else:
                    self._specs = tuple(infer_specs(arrays))
                    self._inferred = True
            self._ensure()
        return self._compiled(*arrays)

    def __getattr__(self, item):
        compiled = object.__getattribute__(self, "_compiled")
        if compiled is None:
            raise AttributeError(
                f"{item!r} is unavailable before compilation — call the "
                f"function once (or pass specs) first")
        return getattr(compiled, item)


def compile(fn: Optional[Callable] = None,
            specs: Optional[Sequence[Any]] = None,
            options: Optional[CompileOptions] = None,
            **kw) -> CompiledFunction:
    """Compile ``fn`` for dynamic shapes through the DISC pipeline.

    ``specs`` declares per-argument shapes with symbolic dims (strings or
    :class:`Dim` objects); omit it to infer from the first call.  All
    remaining keywords are :class:`CompileOptions` fields::

        @disc.compile            # bare decorator, inferred specs
        def f(x, y): ...

        f2 = disc.compile(f, [("B", 64), (64, 32)], backend="pallas")
        lowered = f2.lower()      # inspect DHLO graph + plans
        art = lowered.compile()   # generated dispatcher

    Usable as a decorator (``@disc.compile`` or
    ``@disc.compile(specs=..., backend=...)``).
    """
    if fn is None:  # decorator-with-arguments form
        return lambda f: CompiledFunction(f, specs, options, **kw)
    if not callable(fn):
        raise TypeError("disc.compile: first argument must be callable")
    return CompiledFunction(fn, specs, options, **kw)
