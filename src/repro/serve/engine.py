"""Serving engine: continuous batching over 2-D DISC shape buckets.

The paper's serving problem — requests with varying prompt lengths force
either per-shape recompilation (XLA) or interpretation (Nimble VM) — is
solved here exactly as DISC prescribes, built entirely on the public
``disc.compile`` API:

* **prefill** is ONE single-pass batched artifact with two dynamic dims,
  ``Dim("B", max=max_batch)`` × ``Dim("S", max=max_seq)``: waiting
  requests are admitted together, grouped by prompt-chunk bucket, and one
  launch computes every prompt position's K/V plus last-position logits
  for the whole group (``model.prefill``).  Per-request true lengths ride
  the ``lens`` vector; the gathered KV-cache rows thread through a
  ``TreeSpec`` so the generated dispatch bucket-pads the batch axis of
  every leaf.  Compile count stays O(#(B, S) buckets); hot exact (B, S)
  signatures still escalate (§4.4) to unpadded specializations via
  ``ServeConfig(escalation_threshold=...)``.
* **chunked prefill**: ``ServeConfig(prefill_chunk=...)`` splits long
  prompts into fixed-size chunks interleaved with decode steps
  (``prefill_interleave`` decode steps owed between launches), so a long
  prompt no longer stalls every active decode slot.  The model layer
  supports this through prefill-with-cache-offset entry points
  (``offsets`` = current per-row cache fill).
* **admission** is pluggable (:mod:`repro.serve.policies`): ``"fifo"``,
  ``"shortest-prompt-first"``, ``"priority"`` (``Request.priority``), or
  any callable ordering the waiting queue.
* **decode** is compiled once against the fixed-capacity KV cache; a step
  serves any mix of sequence lengths via the lens vector, and an
  ``active`` row mask gates cache writes so mid-prefill and empty slots
  are never touched by a decode step.
* ``ServeConfig(prefill_mode="replay")`` keeps the previous
  O(prompt_len)-sequential-launches prefill as a benchmark baseline
  (``benchmarks/bench_serve.py`` measures the gap).
* **replicas** (``ServeConfig(replicas=N)``): data-parallel serving.
  The engine owns ``N x max_batch`` KV-cache rows; replica ``r`` owns the
  contiguous slot range ``[r*max_batch, (r+1)*max_batch)``.  Admission
  routes each request (in policy order) to the **least-loaded replica**
  with a free slot; decode is ONE launch over the whole replicated batch
  (the SPMD way: on a mesh the batch axis is partitioned over the
  ``data`` axis, so each replica's rows live on its own devices), so
  tokens per decode launch scale with the replica count.  Stats gain
  per-replica counters (``stats["per_replica"]``).
* **mesh** (``ServeConfig(mesh=..., sharding_profile=...)``): params and
  the KV cache are ``device_put`` per the
  :class:`~repro.dist.profiles.ShardingProfile` (per-replica cache rows
  sharded along ``data``), and the prefill artifact compiles under
  ``CompileOptions(mesh=...)`` so its generated dispatch emits
  ``device_put``-to-sharding on padded buckets (see
  :mod:`repro.dist.spmd`); the total slot count must divide the
  data-parallel axes evenly (checked at engine construction).
* **paged KV** (``ServeConfig(kv_block_size=..., kv_pool_blocks=...)``):
  slots draw ``block_size``-token blocks from a budget-sized physical
  pool (:mod:`repro.serve.paging`) instead of owning a fixed ``max_seq``
  row, so concurrency is bounded by actual token footprint, not
  worst-case rows.  Per-slot block tables ride the compiled artifacts —
  the prefill artifact threads them through a ``TreeSpec`` so they
  bucket-pad with the batch — and the gather into dense rows / scatter
  of fresh positions happens INSIDE the launch, keeping dispatch
  bucket-compiled.  On pool pressure the scheduler preempts a victim
  (lowest priority, newest admission), releases its blocks, and requeues
  the request with prompt+generated tokens: greedy recompute reproduces
  the exact output.  With an unconstrained pool the paged path is
  bit-parity with fixed rows (the baseline, kept as
  ``kv_block_size=None``).
* **speculative decoding** (``ServeConfig(speculative=...,
  speculative_k=...)``): a pluggable proposer
  (:mod:`repro.serve.speculative`; ``"ngram"`` prompt-lookup first,
  draft-model interface stubbed) drafts up to k tokens per slot, and ONE
  widened ``(n_slots, k+1)`` launch of ``model.verify`` (prefill
  semantics, head at every position) scores them all; each slot keeps
  the longest draft prefix matching the model's own greedy argmax plus
  the correction token, and per-slot accept counts advance the ``lens``
  vector.  Greedy accept-or-fix emits exactly the plain-decode tokens —
  only the launch count shrinks.

Both artifacts share one :class:`CompileCache` (entries keyed by
per-artifact fingerprint); compile counts come from the artifacts'
``compile_counts()`` so benchmarks and tests can verify the O(#buckets)
contract end-to-end on a real model.  Every ``stats`` key is documented
in :data:`STATS_KEYS`.
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Set, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..api.options import CompileOptions, Dim, TreeSpec
from ..api.staged import compile as disc_compile
from ..core.bucketing import BucketPolicy, POW2
from ..core.cache import CompileCache
from ..core.codegen import KERNEL_DEMOTIONS
from ..data.pipeline import Request
from ..errors import (CONTROL_EXCEPTIONS, DEFAULT_RETRY, DiscError,
                      RetryPolicy, wrap_launch_error)
from ..frontends.jaxpr_frontend import ArgSpec
from ..ft import faults
from ..ft.supervisor import HeartbeatMonitor
from ..models.registry import (Model, cache_batch_axis, replay_prefill,
                               row_keep_mask)
from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace
from ..obs.clock import CLOCK as _WALL
from .paging import BlockAllocator, PagedKVPool, blocks_for, pick_victim
from .policies import get_admission_policy
from .speculative import get_proposer

# admission groups bucket to powers of two starting at 1 (1, 2, 4, ...,
# clamped to max_batch) — log-many batch buckets
BATCH_POW2 = BucketPolicy(kind="pow2", granule=1)

#: every ``ServeEngine.stats`` key, documented in one place.  Counters
#: reset via :meth:`ServeEngine.reset_stats` except where noted.
STATS_KEYS: Dict[str, str] = {
    "prefill_calls": "prefill launches (any group size)",
    "batched_prefills": "prefill launches serving >1 request in one pass",
    "prefill_chunks": "prefill launches that touched a partially-prefilled "
                      "prompt (chunked prefill active)",
    "prefill_compiles": "prefill artifact compiles, bucket + exact "
                        "(artifact-lifetime: not reset)",
    "prefill_escalations": "§4.4 exact specializations of the prefill "
                           "artifact (artifact-lifetime: not reset)",
    "prefill_bucket_pairs": "distinct (B, S) bucket pairs launched "
                            "(artifact-lifetime: not reset)",
    "decode_steps": "decode launches (whole active batch per launch)",
    "tokens_generated": "tokens produced (incl. each prompt's first token "
                        "at prefill completion)",
    "tokens_per_sec": "tokens_generated / busy seconds inside step()",
    "requests_completed": "requests retired into done",
    "rejected_requests": "requests refused at submit(): prompt longer than "
                         "max_seq, or a worst-case footprint larger than "
                         "the paged pool can ever hold (the rest of the "
                         "batch is still admitted)",
    "peak_active_slots": "max concurrently occupied slots observed (the "
                         "equal-memory concurrency headline for paged KV)",
    "kv_pool_blocks": "paged-KV pool capacity in blocks (0 = fixed rows; "
                      "not reset)",
    "kv_blocks_in_use": "paged-KV blocks currently allocated (not reset)",
    "kv_pool_occupancy": "kv_blocks_in_use / kv_pool_blocks (0.0 under "
                         "fixed rows; not reset)",
    "kv_peak_occupancy": "max pool occupancy fraction observed",
    "kv_preemptions": "slots preempted on pool pressure (request requeued "
                      "with prompt+generated for greedy recompute)",
    "kv_evictions": "blocks reclaimed by preemptions",
    "spec_drafted_tokens": "draft tokens sent to the speculative verify "
                           "launch",
    "spec_accepted_tokens": "draft tokens accepted by verification",
    "mem_launch_bytes": "staging bytes of the last prefill launch (dynamic "
                        "args padded to their (B, S) bucket; not reset)",
    "mem_peak_launch_bytes": "largest single prefill launch observed "
                             "(artifact-lifetime: not reset)",
    "mem_launch_saved_bytes": "cumulative staging bytes saved by bucketing "
                              "vs launching every call at the "
                              "max_batch×max_seq caps (artifact-lifetime: "
                              "not reset)",
    "per_replica": "one dict per replica: admitted, tokens_generated, "
                   "requests_completed, occupied_slots (slot-range "
                   "[r*max_batch, (r+1)*max_batch) counters under "
                   "least-loaded routing)",
    "failed_requests": "requests retired FAILED (permanent launch "
                       "failure, recompute budget exhausted under pool "
                       "pressure, deadline expiry) — reasons in "
                       "``engine.failed[rid]``",
    "retries": "transient launch retries (capped exponential backoff); "
               "transient *compile* retries live in the compile cache's "
               "stats",
    "kernel_demotions": "cluster-kernel / backend demotions journaled "
                        "process-wide during this engine's run (length "
                        "delta of repro.core.codegen.KERNEL_DEMOTIONS)",
    "deadline_expirations": "requests failed because Request.deadline_s "
                            "passed (checked at admission and between "
                            "steps)",
    "replica_drains": "replicas drained after missing the heartbeat "
                      "deadline (slots preempted back to the queue, "
                      "traffic continues on survivors)",
}


@dataclass(frozen=True)
class ServeConfig:
    max_batch: int = 8
    max_seq: int = 512
    # S (prompt/chunk length) buckets; B (admission group) buckets
    prefill_policy: BucketPolicy = POW2
    batch_policy: BucketPolicy = BATCH_POW2
    eos_id: int = 1
    # §4.4 static/dynamic mix on the serving path: exact (B, S) prefill
    # signatures seen at least this many times get an unpadded
    # specialization.  None disables.
    escalation_threshold: Optional[int] = None
    # "batched" = single-pass model.prefill; "replay" = the sequential
    # decode-step replay baseline (one request per launch)
    prefill_mode: str = "batched"
    # split prompts into chunks of at most this many tokens, interleaved
    # with decode steps; None prefills whole prompts in one launch
    prefill_chunk: Optional[int] = None
    # decode steps owed between prefill launches when both are pending
    prefill_interleave: int = 1
    # admission policy name (repro.serve.policies) or callable
    admission: Union[str, Callable] = "fifo"
    # data-parallel replica count: the engine serves replicas*max_batch
    # slots, one decode launch over all of them; admission routes each
    # request to the least-loaded replica's slot range
    replicas: int = 1
    # SPMD placement: a jax.sharding.Mesh + profile name/object (see
    # repro.dist.profiles).  Params/caches are device_put per the
    # profile; the prefill artifact compiles under CompileOptions(mesh=)
    mesh: Optional[Any] = None
    sharding_profile: Optional[Any] = None
    # paged KV pool (repro.serve.paging): block size in tokens, must
    # divide max_seq; None keeps the fixed max_seq-row cache (the parity
    # baseline)
    kv_block_size: Optional[int] = None
    # pool capacity in blocks — the memory budget that replaces
    # n_slots * max_seq.  None = unconstrained (n_slots * max_seq /
    # kv_block_size blocks: bit-parity with fixed rows, no preemption)
    kv_pool_blocks: Optional[int] = None
    # speculative decoding (repro.serve.speculative): proposer name
    # ("ngram") or object with .propose(history, k); None disables
    speculative: Optional[Any] = None
    # max draft tokens per slot per verify launch
    speculative_k: int = 4
    # --- fault-tolerance plane -----------------------------------------
    # bounded recompute: a request preempted (and requeued for greedy
    # recompute) more than this many times is retired FAILED with a
    # PoolExhausted reason instead of spinning in the preemption loop
    # forever (None = unbounded, the pre-taxonomy livelock behavior)
    max_recomputes: Optional[int] = 50
    # transient launch failures retry under this policy before the launch
    # group is failed (None = the shared DEFAULT_RETRY)
    launch_retry: Optional[RetryPolicy] = None
    # replica health: a replica whose last heartbeat (engine.heartbeat(r))
    # is older than this is drained — its slots preempt back to the queue
    # and admission routes around it until a beat restores it.  None
    # disables monitoring (no drain, no heartbeats required)
    heartbeat_deadline_s: Optional[float] = None


@dataclass
class _Slot:
    """One KV-cache row's scheduler state: admitted requests move
    prefill -> decode -> retired (slot freed); under paged KV a slot in
    either live state may also be PREEMPTED on pool pressure — its
    blocks are released and the request requeued (prompt+generated) for
    greedy recompute."""

    rid: int
    tokens: np.ndarray
    plen: int
    remaining: int
    pos: int = 0                  # prompt tokens prefilled so far
    state: str = "prefill"        # "prefill" | "decode"
    generated: List[int] = field(default_factory=list)
    priority: int = 0             # victim ordering on pool pressure
    aseq: int = 0                 # admission sequence (newest preempts first)
    # re-admitted after preemption: the prompt replays previously
    # generated tokens, so the prefill-completion token is NOT the free
    # first token — it consumes max_new budget
    resumed: bool = False


class ServeEngine:
    def __init__(self, model: Model, params, scfg: ServeConfig):
        if scfg.prefill_mode not in ("batched", "replay"):
            raise ValueError(
                f"unknown prefill_mode {scfg.prefill_mode!r} "
                f"(expected 'batched' or 'replay')")
        if scfg.replicas < 1:
            raise ValueError(f"ServeConfig(replicas={scfg.replicas}): "
                             f"need at least 1 replica")
        if scfg.sharding_profile is not None and scfg.mesh is None:
            # mirror CompileOptions: a profile without a mesh is a
            # misconfiguration, not a silent single-device fallback
            raise ValueError(
                "ServeConfig(sharding_profile=...) needs a mesh: pass "
                "ServeConfig(mesh=..., sharding_profile=...)")
        if scfg.kv_block_size is not None:
            if scfg.kv_block_size < 1:
                raise ValueError(
                    f"ServeConfig(kv_block_size={scfg.kv_block_size}): "
                    f"need a positive block size")
            if scfg.max_seq % scfg.kv_block_size != 0:
                raise ValueError(
                    f"ServeConfig(kv_block_size={scfg.kv_block_size}) must "
                    f"divide max_seq={scfg.max_seq}: full block tables "
                    f"cover exactly max_seq positions so the paged "
                    f"artifacts stay shape-identical to fixed rows")
            if scfg.mesh is not None:
                raise ValueError(
                    "paged KV (kv_block_size=...) does not compose with "
                    "mesh sharding yet: the block-id axis has no "
                    "data-parallel layout — drop the mesh or use fixed "
                    "rows")
        if scfg.speculative is not None and scfg.speculative_k < 1:
            raise ValueError(
                f"ServeConfig(speculative_k={scfg.speculative_k}): need "
                f"at least 1 draft token")
        self.model = model
        self.params = params
        self.scfg = scfg
        self.n_slots = scfg.replicas * scfg.max_batch
        self.paged = scfg.kv_block_size is not None
        if self.paged:
            self._mbs = scfg.max_seq // scfg.kv_block_size
            n_blocks = (scfg.kv_pool_blocks
                        if scfg.kv_pool_blocks is not None
                        else self.n_slots * self._mbs)
            self.pool = PagedKVPool(model, n_blocks=n_blocks,
                                    block_size=scfg.kv_block_size)
            self.alloc = BlockAllocator(n_blocks, scfg.kv_block_size,
                                        self.n_slots, self._mbs)
            self.cache = None       # paged state lives in self.pool.tree
        else:
            self._mbs = 0
            self.pool = None
            self.alloc = None
            self.cache = model.init_cache(self.n_slots, scfg.max_seq)
        self.lens = np.zeros((self.n_slots,), np.int32)
        self.slots: List[Optional[_Slot]] = [None] * self.n_slots
        self.queue: List[Request] = []
        self.done: Dict[int, List[int]] = {}
        self.rejected: List[int] = []   # rids refused at submit()
        # fault plane: rid -> failure reason for requests retired FAILED
        # (permanent launch error, PoolExhausted, DeadlineExceeded)
        self.failed: Dict[int, str] = {}
        self._recomputes: Dict[int, int] = {}   # rid -> preempt count
        self._deadlines: Dict[int, float] = {}  # rid -> absolute deadline
        self._clock = time.monotonic            # injectable (tests/docs)
        self._wall = _WALL      # perf timing (busy_s) only
        self._retry = scfg.launch_retry or DEFAULT_RETRY
        self._kdem0 = len(KERNEL_DEMOTIONS)
        self._replica_alive = [True] * scfg.replicas
        self.monitor: Optional[HeartbeatMonitor] = None
        if scfg.heartbeat_deadline_s is not None:
            self.monitor = HeartbeatMonitor(
                [f"replica{r}" for r in range(scfg.replicas)],
                deadline_s=scfg.heartbeat_deadline_s)
            now = self._clock()
            for r in range(scfg.replicas):
                self.monitor.beat(f"replica{r}", t=now)
        self._admit_order = get_admission_policy(scfg.admission)
        self._prefill_impl = (model.prefill if scfg.prefill_mode == "batched"
                              else replay_prefill(model.decode_step))
        self._proposer = get_proposer(scfg.speculative)
        self._decode_credit = 0
        self._bucket_pairs: Set[Tuple[int, int]] = set()
        self._busy_s = 0.0
        self._aseq = 0                  # admission sequence counter
        self._carry: Dict[int, List[int]] = {}  # rid -> generated-so-far
        self._rep_counters = [
            {"admitted": 0, "tokens_generated": 0, "requests_completed": 0}
            for _ in range(scfg.replicas)]

        # SPMD placement: shard the persistent trees once at init (the
        # per-call argument shardings are the prefill artifact's job)
        self.mesh = scfg.mesh
        self._dp_axes: Tuple[str, ...] = ()
        self._put_args = lambda *xs: xs  # decode-input placement
        if self.mesh is not None:
            self._init_mesh(model)

        # one compile cache shared by both artifacts; entries are keyed by
        # per-artifact fingerprint so prefill/decode never collide
        self.compile_cache = CompileCache("serve", max_entries=64)
        pol = dataclasses.replace(
            scfg.prefill_policy,
            overrides=tuple(scfg.prefill_policy.overrides) + (
                ("B", (scfg.batch_policy.kind, scfg.batch_policy.granule)),))
        dim_b = Dim("B", max=self.n_slots)
        popts = CompileOptions(pipeline="jit", name="prefill",
                               policy=pol,
                               escalation_threshold=
                               scfg.escalation_threshold,
                               mesh=scfg.mesh,
                               sharding_profile=scfg.sharding_profile
                               if scfg.mesh is not None else None,
                               cache=self.compile_cache)
        if self.paged:
            # the block pool passes through untouched (None spec); the
            # per-slot block tables ride a TreeSpec so they bucket-pad on
            # B together with tokens/lens — padded rows carry all-null
            # tables and gather/write only the null block
            self._prefill_fn = disc_compile(
                self._prefill_paged,
                specs=[None,                 # params pytree
                       None,                 # block pool pytree
                       TreeSpec({0: "B"}),   # {"tables": (B, max_blocks)}
                       ArgSpec((dim_b, Dim("S", max=scfg.max_seq)),
                               jnp.int32, name="tokens"),
                       ArgSpec((dim_b,), jnp.int32, name="lens"),
                       ArgSpec((dim_b,), jnp.int32, name="offsets")],
                options=popts)
            self._decode_fn = disc_compile(
                self._decode_paged,
                options=CompileOptions(pipeline="jit", name="decode",
                                       cache=self.compile_cache))
        else:
            self._prefill_fn = disc_compile(
                self._prefill_call,
                specs=[None,                 # params pytree
                       TreeSpec({1: "B"}),   # gathered cache rows (L, B, ...)
                       ArgSpec((dim_b, Dim("S", max=scfg.max_seq)),
                               jnp.int32, name="tokens"),
                       ArgSpec((dim_b,), jnp.int32, name="lens"),
                       ArgSpec((dim_b,), jnp.int32, name="offsets")],
                options=popts)
            self._decode_fn = disc_compile(
                self._decode_step,
                options=CompileOptions(pipeline="jit", name="decode",
                                       cache=self.compile_cache))
        self._verify_fn = None
        if self._proposer is not None:
            self._verify_fn = disc_compile(
                self._verify_paged if self.paged else self._verify_call,
                options=CompileOptions(pipeline="jit", name="verify",
                                       cache=self.compile_cache))
        self.stats: Dict[str, Any] = self._zero_stats()
        self._refresh_stats()
        obs_metrics.register_collector("serve", self._obs_stats,
                                       name="engine")
        obs_metrics.register_collector("health", self._obs_health,
                                       name="engine")

    def _init_mesh(self, model: Model) -> None:
        """Shard params + KV cache onto the mesh per the profile: params
        follow the profile's weight layout, cache rows are partitioned
        along the data-parallel axes on their batch axis (axis 1 of the
        layer-stacked ``(L, B, ...)`` leaves) — each replica's rows live
        on its own slice of the ``data`` axis."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ..dist.profiles import get_profile
        from ..dist.spmd import fit_spec

        profile = get_profile(self.scfg.sharding_profile or "dp")
        self.profile = profile
        # the axes the PROFILE shards the batch dim on (not a hardcoded
        # DP set): the cache layout, the slot-divisibility guard, and
        # the decode-input placement must all agree with what the
        # prefill artifact's planner emits for "B"
        self._dp_axes = tuple(a for a in profile.batch_axes()
                              if a in self.mesh.axis_names)
        dp = 1
        for a in self._dp_axes:
            dp *= int(self.mesh.shape[a])
        if dp > 1 and self.n_slots % dp != 0:
            raise ValueError(
                f"replicas*max_batch={self.n_slots} slots must divide the "
                f"batch-sharding mesh axes {self._dp_axes} (size {dp}) "
                f"evenly — adjust replicas/max_batch or the mesh shape")

        def put(x, spec):
            return jax.device_put(x, NamedSharding(
                self.mesh, fit_spec(tuple(x.shape), spec, self.mesh)))

        logical = model.specs() if profile.param_mode == "tp" else None
        pspecs = profile.param_specs(self.params, logical)
        self.params = jax.tree.map(
            lambda s, x: put(x, s), pspecs, self.params,
            is_leaf=lambda s: isinstance(s, P))
        if profile.param_mode == "tp":
            # honor the model's logical cache layout (already rank-
            # aligned with the layer-stacked leaves: batch along the DP
            # axes, heads/sequence along "model")
            cspecs = model.cache_specs()
            self._put_cache = lambda tree: jax.tree.map(
                lambda s, c: put(c, s), cspecs, tree,
                is_leaf=lambda s: isinstance(s, P))
        else:
            def batch_spec(leaf):
                # same batch-axis rule the masking path uses; a leaf
                # with no batch axis stays replicated
                ax = cache_batch_axis(leaf.shape, self.n_slots)
                if ax is None:
                    return P(*([None] * leaf.ndim))
                return profile.batch_leaf_spec(leaf.ndim, ax)

            self._put_cache = lambda tree: jax.tree.map(
                lambda c: put(c, batch_spec(c)), tree)
        self.cache = self._put_cache(self.cache)
        # decode inputs have fixed shapes: precompute their shardings
        # once — the decode loop is the per-token hot path
        dp_spec = self._dp_axes if self._dp_axes else None
        dec_shardings = tuple(
            NamedSharding(self.mesh,
                          fit_spec(shape, P(*((dp_spec,)
                                              + (None,) * (len(shape) - 1))),
                                   self.mesh))
            for shape in ((self.n_slots, 1), (self.n_slots,),
                          (self.n_slots,)))
        self._put_args = lambda *xs: tuple(
            jax.device_put(x, s) for x, s in zip(xs, dec_shardings))

    # ------------------------------------------------------------ device --
    def _prefill_call(self, params, rows, tokens, lens, offsets):
        """Single-pass prefill over a gathered group of cache rows.

        Fresh rows (offset 0) are zeroed first so a previous occupant's
        state can never leak into a new request — positional KV caches
        mask stale entries anyway, but recurrent state is overwritten,
        not masked."""
        fresh = offsets == 0
        rows = jax.tree.map(
            lambda c: jnp.where(row_keep_mask(fresh, c),
                                jnp.zeros_like(c), c), rows)
        logits, rows = self._prefill_impl(params, rows, tokens, lens,
                                          offsets)
        return logits, rows

    def _decode_step(self, params, cache, tokens, lens, active):
        """One decode step; cache writes gated to ``active`` rows so
        mid-prefill and empty slots keep their state untouched."""
        logits, new_cache = self.model.decode_step(params, cache, tokens,
                                                   lens)
        new_cache = jax.tree.map(
            lambda n, o: jnp.where(row_keep_mask(active, o),
                                   n.astype(o.dtype), o),
            new_cache, cache)
        return logits, new_cache

    def _prefill_paged(self, params, pool, tview, tokens, lens, offsets):
        """Paged prefill: gather each group row's blocks into the dense
        fixed-row layout the attention kernels consume, zero fresh rows,
        run the single-pass prefill, then scatter exactly the freshly
        written positions [offset, offset+len) back into the pool.
        Bucket-padded rows carry all-null tables: their gathers see only
        the null block (masked out of every real row by the length
        masks) and their writes land back in it."""
        tables = tview["tables"]
        rows = self.pool.gather(pool, tables)
        fresh = offsets == 0
        rows = jax.tree.map(
            lambda c: jnp.where(row_keep_mask(fresh, c),
                                jnp.zeros_like(c), c), rows)
        logits, rows = self._prefill_impl(params, rows, tokens, lens,
                                          offsets)
        pos = jnp.arange(self.scfg.max_seq)[None, :]
        keep = (pos >= offsets[:, None]) & (pos < (offsets + lens)[:, None])
        return logits, self.pool.scatter(pool, rows, tables, keep)

    def _decode_paged(self, params, pool, tables, tokens, lens, active):
        """Paged decode step: gather, step, scatter only each active
        row's single fresh position ``lens[r]`` (inactive rows write
        nothing, like the fixed path's active gate)."""
        rows = self.pool.gather(pool, tables)
        logits, rows = self.model.decode_step(params, rows, tokens, lens)
        pos = jnp.arange(self.scfg.max_seq)[None, :]
        keep = active[:, None] & (pos == lens[:, None])
        return logits, self.pool.scatter(pool, rows, tables, keep)

    def _verify_call(self, params, cache, tokens, dlens, fills):
        """Speculative verify (fixed rows): one widened chunk pass whose
        per-position argmax comes back to the host — ``ids[r, j]`` is
        the model's greedy token after consuming ``tokens[r, j]``.
        Rows with ``dlens[r] == 0`` write nothing (prefill masks)."""
        logits, new_cache = self.model.verify(params, cache, tokens, dlens,
                                              fills)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), new_cache

    def _verify_paged(self, params, pool, tables, tokens, dlens, fills):
        """Speculative verify over gathered paged rows; the drafted
        positions [fill, fill+dlen) scatter back to the pool."""
        rows = self.pool.gather(pool, tables)
        logits, rows = self.model.verify(params, rows, tokens, dlens,
                                         fills)
        pos = jnp.arange(self.scfg.max_seq)[None, :]
        keep = (pos >= fills[:, None]) & (pos < (fills + dlens)[:, None])
        ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return ids, self.pool.scatter(pool, rows, tables, keep)

    # -------------------------------------------------------------- host --
    def submit(self, reqs: List[Request]) -> None:
        """Queue requests for admission.

        Requests the engine can never serve are rejected gracefully —
        counted in ``stats["rejected_requests"]``, rids recorded in
        ``self.rejected`` — and the REST of the batch is still admitted:

        * prompt longer than ``max_seq`` (chunking would clamp every
          launch under the artifact's S cap and the overflow would
          scatter nowhere: the request would "complete" with garbage);
        * paged mode: worst-case footprint (prompt + max_new tokens)
          needing more blocks than the whole pool holds.

        A rid already pending (queued or in a slot) raises — atomically,
        before anything in the batch is queued: rids are the engine's
        stable identity (admission removal, preemption requeue, the
        ``done`` dict) and a duplicate would silently collapse two
        requests into one ``done`` entry.
        """
        pending = {r.rid for r in self.queue}
        pending.update(s.rid for s in self.slots if s is not None)
        accepted: List[Request] = []
        dropped: List[int] = []
        for r in reqs:
            if r.rid in pending:
                raise ValueError(
                    f"request rid={r.rid} is already pending: rids are "
                    f"the engine's stable identity — leave "
                    f"Request(rid=None) for an auto-assigned monotonic "
                    f"id")
            pending.add(r.rid)
            if len(r.tokens) > self.scfg.max_seq:
                dropped.append(r.rid)
                continue
            if self.paged:
                worst = min(len(r.tokens) + r.max_new_tokens + 1,
                            self.scfg.max_seq)
                if blocks_for(worst, self.scfg.kv_block_size) \
                        > self.alloc.n_blocks:
                    dropped.append(r.rid)
                    continue
            accepted.append(r)
            if r.deadline_s is not None and r.rid not in self._deadlines:
                # absolute deadline fixed at first submission; a
                # preemption requeue of the same rid keeps the original
                self._deadlines[r.rid] = self._clock() + r.deadline_s
        self.stats["rejected_requests"] += len(dropped)
        self.rejected.extend(dropped)
        self.queue.extend(accepted)
        if obs_trace.ACTIVE is not None:
            for r in accepted:
                obs_trace.ACTIVE.async_begin("request.queued", id=r.rid,
                                             prompt_len=len(r.tokens))

    def _replica_of(self, slot: int) -> int:
        return slot // self.scfg.max_batch

    def _admit(self) -> None:
        """Claim free slots for waiting requests in policy order; admitted
        requests enter the prefill state (launched by the next
        :meth:`_prefill_group` calls, grouped by chunk bucket).

        With replicas, each request (still in policy order) is routed to
        the **least-loaded replica** that has a free slot (ties break to
        the lowest replica index), so replica KV caches fill evenly.

        Under paged KV, admission also gates on pool headroom: a request
        is only admitted while the free list covers its first prefill
        chunk (in policy order, no skipping ahead — admitting a slot
        that cannot allocate would just thrash the preemption path).
        Blocks free up as slots retire, so blocked admission is
        pressure, not deadlock."""
        mb = self.scfg.max_batch
        # a drained replica offers no slots until a heartbeat restores it
        free_by_rep = [[i for i in range(r * mb, (r + 1) * mb)
                        if self.slots[i] is None]
                       if self._replica_alive[r] else []
                       for r in range(self.scfg.replicas)]
        n_free = sum(len(f) for f in free_by_rep)
        if not n_free or not self.queue:
            return
        chunk_cap = self.scfg.prefill_chunk or self.scfg.max_seq
        budget = self.alloc.free_blocks if self.paged else 0
        # removal is by rid — the stable identity submit() enforces —
        # never by Request.__eq__ (numpy token arrays make dataclass
        # equality ambiguous-truth-value prone)
        taken: Set[int] = set()
        sp = (obs_trace.ACTIVE.begin("serve.admit", cat="serve")
              if obs_trace.ACTIVE is not None else None)
        for req in self._admit_order(self.queue):
            if len(taken) >= n_free:
                break
            if self.paged:
                need = blocks_for(min(len(req.tokens), chunk_cap),
                                  self.scfg.kv_block_size)
                if need > budget:
                    break
                budget -= need
            taken.add(req.rid)
            rep = min((r for r in range(self.scfg.replicas)
                       if free_by_rep[r]),
                      key=lambda r: (mb - len(free_by_rep[r]), r))
            i = free_by_rep[rep].pop(0)
            toks = np.asarray(req.tokens, np.int32)
            carried = self._carry.pop(req.rid, None)
            self.slots[i] = _Slot(rid=req.rid, tokens=toks,
                                  plen=int(toks.shape[0]),
                                  remaining=req.max_new_tokens,
                                  priority=req.priority,
                                  aseq=self._aseq,
                                  generated=list(carried or ()),
                                  resumed=bool(carried))
            self._aseq += 1
            self.lens[i] = 0
            self._rep_counters[rep]["admitted"] += 1
            if obs_trace.ACTIVE is not None:
                obs_trace.ACTIVE.async_end("request.queued", id=req.rid)
                obs_trace.ACTIVE.async_begin(
                    "request", id=req.rid, replica=rep, slot=i,
                    prompt_len=int(toks.shape[0]),
                    resumed=bool(carried))
        self.queue = [r for r in self.queue if r.rid not in taken]
        if sp is not None:
            sp.end(rids=sorted(taken))

    # -------------------------------------------------------- fault plane --
    def _forget(self, rid: int) -> None:
        """Drop a retired rid's scheduler bookkeeping."""
        self._carry.pop(rid, None)
        self._recomputes.pop(rid, None)
        self._deadlines.pop(rid, None)

    def _fail_request(self, rid: int, reason: str) -> None:
        """Retire ``rid`` FAILED: recorded with its reason, counted, and
        every bookkeeping entry dropped — the rest of the engine keeps
        serving."""
        self.failed[rid] = reason
        self.stats["failed_requests"] += 1
        self._forget(rid)
        if obs_trace.ACTIVE is not None:
            obs_trace.ACTIVE.async_end("request", id=rid, failed=True,
                                       reason=reason)

    def _fail_slot(self, i: int, reason: str) -> None:
        """Fail the request occupying slot ``i`` and free the slot."""
        slot = self.slots[i]
        if self.paged:
            self.alloc.release(i)
        self.slots[i] = None
        self.lens[i] = 0
        self._fail_request(slot.rid, reason)

    def _launch(self, kind: str, fn: Callable, *args):
        """Run one artifact launch under the taxonomy: transient failures
        (backend RESOURCE_EXHAUSTED, injected transients) retry with
        capped exponential backoff; a permanent failure raises a
        classified :class:`~repro.errors.DiscError` for the caller to
        fail exactly the requests in the launch group."""
        sp = (obs_trace.ACTIVE.begin(f"serve.{kind}", cat="serve")
              if obs_trace.ACTIVE is not None else None)
        attempt = 0
        ok = False
        try:
            while True:
                try:
                    if faults.ACTIVE is not None:
                        faults.ACTIVE.check("serve.launch", key=kind)
                    out = fn(*args)
                    ok = True
                    return out
                except CONTROL_EXCEPTIONS:
                    raise
                except DiscError as e:   # already classified (e.g. a
                    err = e              # CompileError out of dispatch)
                except Exception as e:  # noqa: BLE001 — classified below
                    err = wrap_launch_error(e, kind)
                if not err.transient or attempt >= self._retry.max_retries:
                    raise err
                self.stats["retries"] += 1
                obs_metrics.record_event("serve.retry", kind=kind,
                                         attempt=attempt + 1)
                time.sleep(self._retry.delay(attempt))
                attempt += 1
        finally:
            if sp is not None:
                sp.end(attempts=attempt + 1, error=not ok)

    def heartbeat(self, replica: int, *, t: Optional[float] = None) -> None:
        """Record a liveness beat for ``replica`` (requires
        ``ServeConfig(heartbeat_deadline_s=...)``).  A beat from a
        drained replica restores it at the next step."""
        if self.monitor is None:
            raise ValueError(
                "ServeEngine.heartbeat() needs replica health monitoring: "
                "set ServeConfig(heartbeat_deadline_s=...)")
        self.monitor.beat(f"replica{replica}",
                          t=self._clock() if t is None else t)

    def _check_replicas(self) -> None:
        """Drain replicas silent past the heartbeat deadline — their
        slots preempt back to the queue (existing preemption machinery,
        no recompute-budget penalty) and admission routes around them —
        and restore drained replicas that have beaten again."""
        dead = set(self.monitor.dead_hosts(now=self._clock()))
        mb = self.scfg.max_batch
        for r in range(self.scfg.replicas):
            is_dead = f"replica{r}" in dead
            if is_dead and self._replica_alive[r]:
                self._replica_alive[r] = False
                self.stats["replica_drains"] += 1
                obs_metrics.record_event("replica.drain", replica=r)
                for i in range(r * mb, (r + 1) * mb):
                    if self.slots[i] is not None:
                        self._preempt(i, drain=True)
            elif not is_dead and not self._replica_alive[r]:
                self._replica_alive[r] = True   # restored on recovery
                obs_metrics.record_event("replica.restore", replica=r)

    def _check_deadlines(self) -> None:
        """Fail queued and in-slot requests whose deadline passed."""
        if not self._deadlines:
            return
        now = self._clock()
        expired = {rid for rid, d in self._deadlines.items() if now > d}
        if not expired:
            return
        for i, s in enumerate(self.slots):
            if s is not None and s.rid in expired:
                self.stats["deadline_expirations"] += 1
                obs_metrics.record_event("deadline.expire", rid=s.rid)
                self._fail_slot(i, f"DeadlineExceeded: deadline_s passed "
                                   f"after {len(s.generated)} tokens")
        still = [r for r in self.queue if r.rid in expired]
        for r in still:
            self.stats["deadline_expirations"] += 1
            obs_metrics.record_event("deadline.expire", rid=r.rid)
            if obs_trace.ACTIVE is not None:
                obs_trace.ACTIVE.async_end("request.queued", id=r.rid,
                                           failed=True)
            self._fail_request(r.rid, "DeadlineExceeded: deadline_s "
                                      "passed before completion")
        self.queue = [r for r in self.queue if r.rid not in expired]

    def _preempt(self, i: int, *, drain: bool = False) -> None:
        """Evict slot ``i`` on pool pressure (or replica drain): release
        its blocks and requeue the request with prompt+generated as the
        new prompt.  Greedy decoding makes recompute exact — the resumed
        request continues with precisely the tokens it would have
        produced — so preemption trades recompute time for memory, never
        output.

        Pool-pressure preemptions are bounded by
        ``ServeConfig(max_recomputes=...)``: a request past its budget is
        retired FAILED (PoolExhausted) instead of spinning forever.
        Drain preemptions (replica fault, not memory pressure) don't
        consume the budget."""
        slot = self.slots[i]
        if not drain and self.scfg.max_recomputes is not None:
            n = self._recomputes.get(slot.rid, 0) + 1
            if n > self.scfg.max_recomputes:
                if self.paged:
                    self.stats["kv_evictions"] += len(self.alloc.owned(i))
                self._fail_slot(
                    i, f"PoolExhausted: preempted {n - 1} times under "
                       f"pool pressure (max_recomputes="
                       f"{self.scfg.max_recomputes})")
                return
            self._recomputes[slot.rid] = n
        if self.paged:
            freed = self.alloc.release(i)
            if not drain:
                self.stats["kv_preemptions"] += 1
                self.stats["kv_evictions"] += freed
        obs_metrics.record_event("preempt", rid=slot.rid, slot=i,
                                 drain=drain)
        if obs_trace.ACTIVE is not None:
            obs_trace.ACTIVE.async_end("request", id=slot.rid,
                                       preempted=True)
            obs_trace.ACTIVE.async_begin("request.queued", id=slot.rid,
                                         prompt_len=int(slot.plen),
                                         requeued=True)
        toks = slot.tokens
        if slot.generated:
            toks = np.concatenate(
                [toks, np.asarray(slot.generated, np.int32)])
        self._carry[slot.rid] = list(slot.generated)
        self.queue.append(Request(rid=slot.rid, tokens=toks,
                                  max_new_tokens=slot.remaining,
                                  priority=slot.priority))
        self.slots[i] = None
        self.lens[i] = 0

    def _ensure_blocks(self, i: int, n_tokens: int,
                       protect: Set[int]) -> bool:
        """Grow slot ``i``'s allocation to cover ``n_tokens`` positions,
        preempting victims (lowest priority, then newest admission) on
        pool pressure.  ``protect`` shields slots already committed to
        the launch being assembled; returns False only when every
        remaining block owner is protected."""
        while not self.alloc.ensure(i, n_tokens):
            cands = [(j, s.priority, s.aseq)
                     for j, s in enumerate(self.slots)
                     if s is not None and j != i and j not in protect
                     and self.alloc.owned(j)]
            v = pick_victim(cands)
            if v is None:
                return False
            self._preempt(v)
        return True

    def _prefill_group(self) -> None:
        """One prefill launch: group prefill-state slots by the bucket of
        their next chunk length and launch the largest group in a single
        batched pass (replay mode launches one request at a time)."""
        chunk_cap = self.scfg.prefill_chunk or self.scfg.max_seq
        groups: Dict[int, List[Tuple[int, int]]] = {}
        for i, s in enumerate(self.slots):
            if s is None or s.state != "prefill":
                continue
            cl = min(s.plen - s.pos, chunk_cap)
            sb = min(self.scfg.prefill_policy.bucket("S", max(cl, 1)),
                     self.scfg.max_seq)
            groups.setdefault(sb, []).append((i, cl))
        if not groups:
            return
        _, members = max(groups.items(), key=lambda kv: (len(kv[1]), -kv[0]))
        if self.scfg.prefill_mode == "replay":
            members = members[:1]
        if self.paged:
            # claim blocks for every member's chunk before building the
            # launch; a member that cannot allocate even after preempting
            # every unprotected victim sheds itself back to the queue
            # (admission re-gates it on pool headroom; the bounded
            # recompute budget turns a permanently starved slot into a
            # PoolExhausted failure instead of a livelock)
            kept = []
            for i, cl in members:
                s = self.slots[i]
                if s is None or s.state != "prefill":
                    continue    # preempted while assembling this launch
                protect = {j for j, _ in kept} | {i}
                if self._ensure_blocks(i, s.pos + cl, protect):
                    kept.append((i, cl))
                else:
                    self._preempt(i)
            members = kept
            if not members:
                return
        idx = np.asarray([i for i, _ in members])
        nb = len(members)
        smax = max(cl for _, cl in members)
        bucket = (min(self.scfg.batch_policy.bucket("B", nb), self.n_slots),
                  min(self.scfg.prefill_policy.bucket("S", smax),
                      self.scfg.max_seq))
        tr = obs_trace.ACTIVE
        group = (tr.begin("serve.prefill_group", cat="serve",
                          rids=[self.slots[i].rid for i, _ in members],
                          lens=[cl for _, cl in members], B=bucket[0],
                          S=bucket[1]) if tr is not None else None)
        tokens = np.zeros((nb, smax), np.int32)
        lens = np.zeros((nb,), np.int32)
        offsets = np.zeros((nb,), np.int32)
        for r, (i, cl) in enumerate(members):
            s = self.slots[i]
            tokens[r, :cl] = s.tokens[s.pos:s.pos + cl]
            lens[r] = cl
            offsets[r] = s.pos

        try:
            if self.paged:
                tview = {"tables": self.alloc.table()[idx]}
                logits, new_pool = self._launch(
                    "prefill", self._prefill_fn, self.params,
                    self.pool.tree, tview, tokens, lens, offsets)
            else:
                sp = (tr.begin("serve.rows.gather", cat="serve")
                      if tr is not None else None)
                rows = jax.tree.map(
                    lambda c: c[:, idx] if c.ndim > 1 else c, self.cache)
                if sp is not None:
                    sp.end()
                logits, new_rows = self._launch(
                    "prefill", self._prefill_fn, self.params, rows, tokens,
                    lens, offsets)
        except DiscError as e:
            # a failed launch fails ONLY this launch group; queued and
            # decode-state requests are untouched
            for i, _ in members:
                self._fail_slot(i, f"LaunchError(prefill): {e}")
            if group is not None:
                group.end(error=True)
            return
        if self.paged:
            self.pool.tree = new_pool
        else:
            sp = (tr.begin("serve.rows.scatter", cat="serve")
                  if tr is not None else None)
            self.cache = jax.tree.map(
                lambda full, row: full.at[:, idx].set(
                    row[:, :nb].astype(full.dtype))
                if full.ndim > 1 else full,
                self.cache, new_rows)
            if self.mesh is not None:
                # the eager scatter above may change leaf shardings; pin
                # the cache back to its planned layout so the decode
                # artifact's jit entries never retrace on a sharding flip
                self.cache = self._put_cache(self.cache)
            if sp is not None:
                sp.end()
        sp = (tr.begin("serve.first_tokens", cat="serve")
              if tr is not None else None)
        last = np.asarray(logits[:nb])
        if sp is not None:
            sp.end()

        self._bucket_pairs.add(bucket)
        self.stats["prefill_calls"] += 1
        if nb > 1:
            self.stats["batched_prefills"] += 1
        chunked = bool(np.any(offsets > 0))
        for r, (i, cl) in enumerate(members):
            s = self.slots[i]
            s.pos += cl
            self.lens[i] = s.pos
            if s.pos >= s.plen:
                s.state = "decode"
                s.generated.append(int(np.argmax(last[r])))
                if tr is not None and len(s.generated) == 1:
                    tr.instant("request.first_token", cat="serve",
                               rid=s.rid)
                if s.resumed:
                    # a resumed prompt replays previously generated
                    # tokens: its completion token is a fresh one and
                    # consumes budget (the free first token was already
                    # granted by the original prefill)
                    s.remaining -= 1
                    s.resumed = False
                self.stats["tokens_generated"] += 1
                self._rep_counters[self._replica_of(i)][
                    "tokens_generated"] += 1
                self._maybe_retire(i)
            else:
                chunked = True
        if chunked:
            self.stats["prefill_chunks"] += 1
        if group is not None:
            group.end()

    def _decode(self) -> None:
        """One decode launch over ALL replicas' rows — the tokens-per-
        launch scaling replicas buy; on a mesh the batch axis is
        partitioned along ``data``, so each replica computes its own
        rows.  With a proposer configured, the launch is the widened
        speculative verify instead."""
        active_idx = [i for i, s in enumerate(self.slots)
                      if s is not None and s.state == "decode"]
        sp = (obs_trace.ACTIVE.begin(
            "serve.decode_step", cat="serve",
            rids=[self.slots[i].rid for i in active_idx])
            if obs_trace.ACTIVE is not None else None)
        try:
            if self._proposer is not None:
                self._decode_speculative(active_idx)
            else:
                self._decode_plain(active_idx)
        finally:
            if sp is not None:
                sp.end()

    def _wait_tokens(self, x) -> np.ndarray:
        """The sampled tokens on the host: the wait for the step."""
        sp = (obs_trace.ACTIVE.begin("serve.tokens", cat="serve")
              if obs_trace.ACTIVE is not None else None)
        out = np.asarray(x, np.int32)
        if sp is not None:
            sp.end()
        return out

    def _decode_plain(self, active_idx: List[int]) -> None:
        if self.paged:
            # every active row writes position lens[r]: claim the block
            # first, preempting on pressure; a row that cannot allocate
            # even then (all owners protected) sheds itself
            protect: Set[int] = set()
            for i in list(active_idx):
                s = self.slots[i]
                if s is None or s.state != "decode":
                    continue
                if self._ensure_blocks(i, int(self.lens[i]) + 1, protect):
                    protect.add(i)
                else:
                    self._preempt(i)
            active_idx = [i for i in active_idx
                          if self.slots[i] is not None
                          and self.slots[i].state == "decode"]
            if not active_idx:
                return
        tokens = np.zeros((self.n_slots, 1), np.int32)
        active = np.zeros((self.n_slots,), bool)
        for i in active_idx:
            tokens[i, 0] = self.slots[i].generated[-1]
            active[i] = True
        try:
            if self.paged:
                logits, self.pool.tree = self._launch(
                    "decode", self._decode_fn, self.params, self.pool.tree,
                    jnp.asarray(self.alloc.table()), jnp.asarray(tokens),
                    jnp.asarray(self.lens), jnp.asarray(active))
            else:
                t, l, a = self._put_args(jnp.asarray(tokens),
                                         jnp.asarray(self.lens),
                                         jnp.asarray(active))
                logits, self.cache = self._launch(
                    "decode", self._decode_fn, self.params, self.cache,
                    t, l, a)
        except DiscError as e:
            for i in active_idx:
                self._fail_slot(i, f"LaunchError(decode): {e}")
            return
        nxt = self._wait_tokens(jnp.argmax(logits[:, 0], axis=-1))
        self.stats["decode_steps"] += 1
        for i in active_idx:
            slot = self.slots[i]
            self.lens[i] += 1
            slot.generated.append(int(nxt[i]))
            slot.remaining -= 1
            self.stats["tokens_generated"] += 1
            self._rep_counters[self._replica_of(i)]["tokens_generated"] += 1
            self._maybe_retire(i)

    def _decode_speculative(self, active_idx: List[int]) -> None:
        """One widened (n_slots, k+1) verify launch: slot r's pending
        token plus up to k drafted tokens; the longest draft prefix
        matching the model's greedy argmax is accepted and the model's
        own token at the first divergence is the correction.  Accept
        counts advance the ``lens`` vector — cache fill moves by
        1 + accepted per launch instead of 1."""
        k = self.scfg.speculative_k
        tokens = np.zeros((self.n_slots, k + 1), np.int32)
        dlens = np.zeros((self.n_slots,), np.int32)
        drafts: Dict[int, np.ndarray] = {}
        protect: Set[int] = set()
        live: List[int] = []
        for i in list(active_idx):
            s = self.slots[i]
            if s is None or s.state != "decode":
                continue    # preempted while assembling this launch
            fill = int(self.lens[i])
            # drafted chunk must fit the row (fill + 1 + drafts <=
            # max_seq - 1) and never draft past the remaining budget
            cap = min(k, self.scfg.max_seq - fill - 2, s.remaining - 1)
            dr = np.zeros((0,), np.int32)
            if cap > 0:
                hist = np.concatenate(
                    [s.tokens, np.asarray(s.generated, np.int32)])
                dr = np.asarray(self._proposer.propose(hist, cap),
                                np.int32).reshape(-1)[:cap]
            dl = 1 + int(dr.shape[0])
            if self.paged:
                if not self._ensure_blocks(i, fill + dl, protect):
                    dr = dr[:0]     # shrink the ask to the bare step
                    dl = 1
                    if not self._ensure_blocks(i, fill + 1, protect):
                        self._preempt(i)
                        continue
                protect.add(i)
            tokens[i, 0] = s.generated[-1]
            tokens[i, 1:dl] = dr
            dlens[i] = dl
            drafts[i] = dr
            live.append(i)
        if not live:
            return
        fills = self.lens.copy()
        try:
            if self.paged:
                ids, self.pool.tree = self._launch(
                    "verify", self._verify_fn, self.params, self.pool.tree,
                    jnp.asarray(self.alloc.table()), jnp.asarray(tokens),
                    jnp.asarray(dlens), jnp.asarray(fills))
            else:
                ids, self.cache = self._launch(
                    "verify", self._verify_fn, self.params, self.cache,
                    jnp.asarray(tokens), jnp.asarray(dlens),
                    jnp.asarray(fills))
        except DiscError as e:
            for i in live:
                self._fail_slot(i, f"LaunchError(verify): {e}")
            return
        ids = self._wait_tokens(ids)
        self.stats["decode_steps"] += 1
        for i in live:
            s = self.slots[i]
            dr = drafts[i]
            dl = int(dlens[i])
            a = 0
            while a < dl - 1 and int(ids[i, a]) == int(dr[a]):
                a += 1
            # emitted = accepted drafts + the model's correction token;
            # rejected positions beyond fill+a+1 stay stale in the cache
            # but are masked (>= fill) until overwritten
            emitted = [int(x) for x in dr[:a]] + [int(ids[i, a])]
            self.stats["spec_drafted_tokens"] += dl - 1
            self.stats["spec_accepted_tokens"] += a
            kept = 0
            for tok in emitted:
                s.generated.append(tok)
                s.remaining -= 1
                kept += 1
                self.stats["tokens_generated"] += 1
                self._rep_counters[self._replica_of(i)][
                    "tokens_generated"] += 1
                if tok == self.scfg.eos_id or s.remaining <= 0:
                    break
            self.lens[i] = int(fills[i]) + kept
            self._maybe_retire(i)

    def _maybe_retire(self, i: int) -> None:
        slot = self.slots[i]
        if (slot.remaining <= 0 or slot.generated[-1] == self.scfg.eos_id
                or self.lens[i] >= self.scfg.max_seq - 1):
            self.done[slot.rid] = slot.generated
            self.stats["requests_completed"] += 1
            self._forget(slot.rid)
            if obs_trace.ACTIVE is not None:
                obs_trace.ACTIVE.async_end("request", id=slot.rid,
                                           tokens=len(slot.generated))
            self._rep_counters[self._replica_of(i)][
                "requests_completed"] += 1
            if self.paged:
                # normal retirement, not an eviction: blocks just return
                self.alloc.release(i)
            self.slots[i] = None
            self.lens[i] = 0

    def step(self) -> None:
        """One engine iteration: admit, then either a prefill launch or a
        decode step — the ``prefill_interleave`` budget decides which when
        both kinds of work are pending."""
        t0 = self._wall()
        sp = (obs_trace.ACTIVE.begin("serve.step", cat="serve")
              if obs_trace.ACTIVE is not None else None)
        try:
            if self.monitor is not None:
                self._check_replicas()
            self._check_deadlines()
            self._admit()
            has_p = any(s is not None and s.state == "prefill"
                        for s in self.slots)
            has_d = any(s is not None and s.state == "decode"
                        for s in self.slots)
            if has_p and (not has_d or self._decode_credit <= 0):
                self._prefill_group()
                self._decode_credit = max(self.scfg.prefill_interleave, 0)
            elif has_d:
                self._decode()
                self._decode_credit -= 1
            self._busy_s += self._wall() - t0
            self._refresh_stats()
        finally:
            if sp is not None:
                sp.end()

    def run_until_done(self, max_steps: int = 10_000) -> Dict[int, List[int]]:
        steps = 0
        while (self.queue or any(s is not None for s in self.slots)):
            self.step()
            steps += 1
            if steps > max_steps:
                break
        return self.done

    # ------------------------------------------------------ introspection --
    def report(self) -> Dict[str, Any]:
        """Engine health + stats in one structured view.

        ``report()["health"]`` is the fault plane's summary: replica
        liveness (with last-beat ages under monitoring), FAILED requests
        with their reasons, the fault counters, compile-cache
        retry/escalation-failure totals, and any kernel/backend
        demotions journaled during this engine's run."""
        return {"health": self._obs_health(), "stats": dict(self.stats),
                "compiles": self.compile_counts()}

    def _obs_health(self) -> Dict[str, Any]:
        """The ``report()["health"]`` payload — also registered as the
        pull collector behind ``disc.observe()["health"]["engine"]``."""
        now = self._clock()
        replicas = []
        for r, alive in enumerate(self._replica_alive):
            entry: Dict[str, Any] = {"replica": r, "alive": bool(alive)}
            if self.monitor is not None:
                seen = self.monitor.last_seen[f"replica{r}"]
                entry["last_beat_age_s"] = round(now - seen, 3)
            replicas.append(entry)
        cs = self.compile_cache.stats
        return {
            "alive_replicas": int(sum(self._replica_alive)),
            "replicas": replicas,
            "failed": {rid: self.failed[rid]
                       for rid in sorted(self.failed)},
            "counters": {k: self.stats[k] for k in
                         ("failed_requests", "retries", "kernel_demotions",
                          "deadline_expirations", "replica_drains")},
            "compile": {"retries": cs.retries,
                        "escalation_failures": cs.escalation_failures},
            "kernel_demotions": list(KERNEL_DEMOTIONS[self._kdem0:]),
        }

    def _obs_stats(self) -> Dict[str, Any]:
        """Pull collector behind ``disc.observe()["serve"]["engine"]`` —
        the same counters as :attr:`stats`, refreshed at snapshot time."""
        self._refresh_stats()
        out = dict(self.stats)
        out["per_replica"] = [dict(c) for c in self.stats["per_replica"]]
        return out

    def compile_counts(self) -> Dict[str, Dict[str, int]]:
        """Per-artifact compile counts (``{"bucket", "exact", "total"}``
        each) — the observable O(#buckets) contract."""
        zero = {"bucket": 0, "exact": 0, "total": 0}

        def counts(fn):
            try:
                return fn.compile_counts()
            except AttributeError:  # not compiled yet (no calls)
                return dict(zero)

        out = {"prefill": counts(self._prefill_fn),
               "decode": counts(self._decode_fn)}
        if self._verify_fn is not None:
            out["verify"] = counts(self._verify_fn)
        return out

    def _zero_stats(self) -> Dict[str, Any]:
        """A typed zero value for every :data:`STATS_KEYS` entry: plain
        counters are ints, rate/occupancy keys are floats, and
        ``per_replica`` is a fresh list of per-replica counter dicts —
        never the scalar 0 a uniform ``= 0`` sweep would leave behind."""
        z: Dict[str, Any] = {k: 0 for k in STATS_KEYS}
        for k in ("tokens_per_sec", "kv_pool_occupancy",
                  "kv_peak_occupancy"):
            z[k] = 0.0
        z["per_replica"] = [
            {"admitted": 0, "tokens_generated": 0,
             "requests_completed": 0, "occupied_slots": 0}
            for _ in range(self.scfg.replicas)]
        return z

    def reset_stats(self) -> None:
        """Zero the per-run counters (benchmark warmup boundary), each to
        its documented **type** via :meth:`_zero_stats` — the old uniform
        ``= 0`` sweep clobbered ``per_replica``'s list-of-dicts to an
        int.  Artifact-lifetime counters — compiles, escalations, bucket
        pairs, pool capacity/in-use — are re-derived and keep
        accumulating."""
        self.stats.update(self._zero_stats())
        self._rep_counters = [
            {"admitted": 0, "tokens_generated": 0, "requests_completed": 0}
            for _ in range(self.scfg.replicas)]
        self._busy_s = 0.0
        self._kdem0 = len(KERNEL_DEMOTIONS)   # demotion delta restarts
        self._refresh_stats()

    def _refresh_stats(self) -> None:
        pc = self.compile_counts()["prefill"]
        self.stats["prefill_compiles"] = pc["total"]
        self.stats["prefill_escalations"] = pc["exact"]
        self.stats["prefill_bucket_pairs"] = len(self._bucket_pairs)
        self.stats["kernel_demotions"] = len(KERNEL_DEMOTIONS) - self._kdem0
        occ = sum(s is not None for s in self.slots)
        self.stats["peak_active_slots"] = max(
            self.stats["peak_active_slots"], occ)
        if self.paged:
            self.stats["kv_pool_blocks"] = self.alloc.n_blocks
            self.stats["kv_blocks_in_use"] = self.alloc.used_blocks
            frac = self.alloc.used_blocks / self.alloc.n_blocks
            self.stats["kv_pool_occupancy"] = frac
            self.stats["kv_peak_occupancy"] = max(
                self.stats["kv_peak_occupancy"], frac)
        try:
            # staging accounting off the prefill dispatch (see
            # DispatchMemStats): padded launch bytes vs the cap worst case
            ms = self._prefill_fn._mstats
            self.stats["mem_launch_bytes"] = ms.last_bytes
            self.stats["mem_peak_launch_bytes"] = ms.peak_bytes
            self.stats["mem_launch_saved_bytes"] = ms.saved_bytes
        except AttributeError:  # not compiled yet (no calls)
            pass
        mb = self.scfg.max_batch
        self.stats["per_replica"] = [
            dict(c, occupied_slots=sum(
                s is not None
                for s in self.slots[r * mb:(r + 1) * mb]))
            for r, c in enumerate(self._rep_counters)]
        if self._busy_s > 0:
            self.stats["tokens_per_sec"] = \
                self.stats["tokens_generated"] / self._busy_s
