"""Continuous-batching scheduler: slot lifecycle, admission & resilience.

``ServeEngine`` packs up to ``max_slots`` concurrent requests into one
slot-indexed decode cache (``slots.py``) and advances all of them together
with the compiled block decode (``engine.decode_scan`` — one device
dispatch per ``decode_block`` tokens, not per token).  Queued requests are
admitted into free slots *between* blocks: admission prefills the request
at batch 1 (the chunked Taylor scan hands its final moment state straight
to the slot via ``return_state=True``) and splices the state in with
``write_slot`` while every other slot keeps its in-flight context.

Slot lifecycle (see DESIGN.md §Serving):

  FREE --admit(prefill+write_slot)--> ACTIVE --eos / budget--> RETIRED
   ^                                    |                        |
   |                         quarantine / deadline               |
   +------------------------------ clear_slot -------------------+

Per-token cost is independent of how requests arrive: a request admitted
into a busy batch produces the same tokens as a solo run (tested), because
slots never interact — every op in the decode step is batch-parallel.

Failure semantics (docs/serving.md §Failure semantics): every submitted
request ends in exactly one terminal ``Status`` — OK, DEGRADED,
TIMED_OUT, FAILED or REJECTED — retrievable as a ``RequestResult`` via
``run(return_results=True)``.  The ``ResiliencePolicy`` knobs control
admission (bounded queue with shedding, overload degradation), deadlines
and queue-TTL (enforced at decode-block boundaries), bounded
retry-with-backoff after quarantine or dispatch loss, and the
``state_health`` sweep that quarantines slots whose moment/KV/SSM state
went non-finite without perturbing co-batched slots.  A seeded
``serve.faults.FaultPlan`` exercises all of it deterministically.

Two orthogonal extensions (docs/serving.md):

* ``mesh=`` runs the engine sharded — tensor-parallel weights
  (``param_specs``), the slot axis data-sharded (``slot_cache_specs``),
  cache-producing dispatches pinned + donated; decode output is
  token-identical to the single-device engine (tested).
* ``prefill_chunk=`` admits long prompts chunk-by-chunk (a PREFILLING
  slot is reserved and fed one chunk per engine step), so admission
  interleaves with in-flight decode instead of stalling it.
* ``SchedulerPolicy.speculative_k`` / ``Request.speculative_k`` turn on
  speculative decoding (``serve/speculative.py``): greedy slots draft k
  tokens per round and verify them in one chunked dispatch, co-batched
  with plain decode/prefill — token-identical by construction
  (docs/serving.md §Speculative decoding).
"""

from __future__ import annotations

import contextlib
import dataclasses
import enum
import functools
import itertools
import time
from collections import Counter, deque
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig
from repro.serve import engine as engine_mod
from repro.serve import slots as slots_mod
from repro.serve import speculative as spec_mod
from repro.serve.engine import (
    _jitted_prefill,
    _jitted_prefill_chunk,
    sample_tokens,
)

Array = jax.Array

# Engine phases ``ServeEngine.timings()`` reports, each also a
# ``serve.<phase>`` span in a profiler trace (docs/serving.md
# §Observability).
PHASES = ("step", "admit", "prefill", "prefill_chunk", "decode", "health")


class Status(enum.Enum):
    """Terminal outcome of one request (the status lattice).

    Every submitted request ends in exactly one of these:

      * ``OK``        — full output produced (eos or budget).
      * ``DEGRADED``  — full output, but produced under the overload
        degradation policy (budget clamped / chunked prefill forced);
        tokens are still exact for what was generated.
      * ``TIMED_OUT`` — deadline or queue-TTL expired; ``tokens`` holds
        the prefix accepted before expiry.
      * ``FAILED``    — retries exhausted after quarantine/dispatch loss;
        ``tokens`` holds the accepted prefix, ``error`` the last cause.
      * ``REJECTED``  — refused at submit (validation or load shedding);
        no tokens.
    """

    OK = "ok"
    DEGRADED = "degraded"
    TIMED_OUT = "timed_out"
    FAILED = "failed"
    REJECTED = "rejected"


@dataclasses.dataclass(frozen=True)
class RequestResult:
    """Typed terminal outcome of one request.

    Attributes:
      status: terminal ``Status``.
      tokens: new tokens produced (``[n] int32``; the accepted prefix for
        TIMED_OUT/FAILED, empty for REJECTED).  Tokens of OK/DEGRADED
        greedy requests are token-identical to a fault-free run (tested).
      error: human-readable cause for non-successful statuses.
      retries: number of re-prefill retries the request consumed.
      preemptions: times the request was preempted back to the queue.
      submitted_at: engine-clock time of ``submit`` (virtual seconds under
        the load harness's ``VirtualClock`` — serve/load.py).
      first_token_at: engine-clock time the first output token existed
        (end of prefill); None if the request never reached a slot.
      finished_at: engine-clock time the terminal status was recorded.
      admitted_at: engine-clock time the request first got a slot (or its
        chunked admission began); None if it never did.  With
        ``submitted_at`` it gives the queue wait, with ``first_token_at``
        the admission (prefill) time.
    """

    status: Status
    tokens: np.ndarray
    error: Optional[str] = None
    retries: int = 0
    preemptions: int = 0
    submitted_at: Optional[float] = None
    first_token_at: Optional[float] = None
    finished_at: Optional[float] = None
    admitted_at: Optional[float] = None


class RequestRejected(ValueError):
    """Typed submit-time rejection (validation or shedding).

    Subclasses ``ValueError`` so pre-resilience callers that caught the
    untyped validation errors keep working.

    Attributes:
      reason: machine-readable code (``empty_prompt``, ``bad_budget``,
        ``prompt_too_long``, ``over_capacity``, ``bad_extras``,
        ``bad_speculative_k``, ``unknown_draft``, ``draft_unavailable``,
        ``queue_full``).
      rid: request id under which the engine recorded the ``REJECTED``
        ``RequestResult`` (for terminal-status audits).
    """

    def __init__(self, message: str, reason: str, rid: Optional[int] = None):
        super().__init__(message)
        self.reason = reason
        self.rid = rid


class QueueOverflow(RequestRejected):
    """Raised by ``submit`` when the bounded queue sheds the request
    (``ResiliencePolicy.max_queue`` reached)."""

    def __init__(self, message: str, rid: Optional[int] = None):
        super().__init__(message, reason="queue_full", rid=rid)


@dataclasses.dataclass(frozen=True)
class ResiliencePolicy:
    """Admission, deadline, and recovery knobs of the serve engine.

    The defaults reproduce the pre-resilience engine exactly on a healthy
    run (unbounded queue, no degradation) while keeping the health sweep
    and bounded retries armed.

    Attributes:
      max_queue: bounded queue depth (queued + awaiting-retry); a submit
        beyond it is shed with ``QueueOverflow``.  None = unbounded.
      degrade_queue_depth: queue depth at or above which new submissions
        are admitted DEGRADED.  None = never degrade.
      degraded_max_new_tokens: budget clamp applied to degraded
        submissions (None = no clamp).
      degrade_prefill_chunk: per-request chunked-prefill size forced on
        degraded submissions, so long overload prompts cannot monopolise
        the device (None = engine default).
      max_retries: re-prefill attempts per request after quarantine or
        dispatch loss before it finalises FAILED.
      retry_backoff_blocks: backoff base — retry ``r`` waits
        ``retry_backoff_blocks * 2**(r-1)`` decode blocks before
        re-entering the queue (at its front).
      max_dispatch_retries: in-place re-dispatch attempts of one decode
        block (safe only while the donated cache is still alive); past
        them the engine rebuilds the cache and requeues live requests.
      health_check_every: run the ``state_health`` sweep every N decode
        blocks (0 disables sweeping).
    """

    max_queue: Optional[int] = None
    degrade_queue_depth: Optional[int] = None
    degraded_max_new_tokens: Optional[int] = None
    degrade_prefill_chunk: Optional[int] = None
    max_retries: int = 2
    retry_backoff_blocks: int = 1
    max_dispatch_retries: int = 2
    health_check_every: int = 1


@dataclasses.dataclass(frozen=True)
class SchedulerPolicy:
    """SLO-driven scheduling knobs (docs/serving.md §Scheduling).

    The defaults reproduce the original FIFO head-of-line scheduler
    exactly: strict arrival-order admission, one prefill chunk per engine
    step, fixed chunk size, no preemption.  Turning the knobs on trades
    strict FIFO fairness for tail-latency control under load — the
    policies ``benchmarks/bench_load.py`` measures against each other.

    Attributes:
      priority_admission: admit by ``(Request.priority, arrival)`` instead
        of strict FIFO, and keep admitting short/high-priority requests
        into remaining free slots while a long chunked prefill is in
        flight (lifts the head-of-line starvation of the FIFO scheduler —
        pinned by ``tests/test_load.py``).
      decode_per_prefill: decode blocks run per prefill chunk of an
        in-flight chunked admission (interleave ratio).  1 = strict
        alternation (the original behaviour); N > 1 protects the
        per-token latency of in-flight slots at the cost of admission
        latency.  While no slot is actively decoding, chunks always feed
        every step — throttling an idle engine would be pure waste.
      fat_chunk_depth: queue depth at which chunked-prefill chunks FATTEN:
        the chunk size is multiplied by a power-of-two factor
        (``1 + depth // fat_chunk_depth``, bucketed, capped at
        ``fat_chunk_max``) so a deep backlog is drained with fewer, fatter
        dispatches — the measured chunked-prefill overhead is per-dispatch
        (BENCH_serve_sharded.json).  None = fixed chunk size.
      fat_chunk_max: cap on the fattening factor (power of two).
      preemption: preempt over-budget low-priority ACTIVE slots back to
        the queue when a strictly higher-priority request is waiting and
        no slot is free.  The slot's decode state is saved with
        ``read_slot`` (state handoff — O(1) bytes on the taylor backend)
        and spliced back with ``write_slot`` on re-admission, so the
        resumed request continues token-identically WITHOUT re-prefill.
      preempt_min_tokens: a slot only becomes preemptible after producing
        this many tokens (anti-thrash floor).
      max_preemptions: per-request preemption bound (prevents a stream of
        high-priority arrivals from starving a low-priority request
        forever).
      speculative_k: engine-wide speculative-decoding depth — greedy slots
        draft k tokens per round and verify them in ONE chunked dispatch
        (``serve/speculative.py``; docs/serving.md §Speculative decoding).
        0 (the default) disables speculation; ``Request.speculative_k``
        overrides per request.  Sampled requests always decode plainly.
      speculative_draft: default draft proposer name (``"ngram"`` — the
        weight-free prompt-lookup baseline — or ``"order1"``, the
        same-weights order-1 self-draft on backends whose
        ``draft_config`` provides one).  ``Request.draft`` overrides per
        request; unknown names are rejected at submit time.
    """

    priority_admission: bool = False
    decode_per_prefill: int = 1
    fat_chunk_depth: Optional[int] = None
    fat_chunk_max: int = 4
    preemption: bool = False
    preempt_min_tokens: int = 1
    max_preemptions: int = 2
    speculative_k: int = 0
    speculative_draft: str = "ngram"


@dataclasses.dataclass
class Request:
    """One generation request.

    Attributes:
      tokens: prompt token ids, ``[n]`` int (list or ndarray).
      max_new_tokens: generation budget, counting the first token sampled
        from the prefill logits.
      temperature: 0 = greedy argmax; > 0 samples at this temperature.
      top_k: > 0 restricts sampling to the k highest-logit tokens.
      eos_id: stop token — generation ends once it is emitted (the eos
        token itself is included in the output).  None = never stop early.
      extras: extra model inputs with a leading batch-1 axis, e.g.
        ``image_embeds [1, n_img, vision_dim]`` (vlm) or ``audio_frames``
        (encdec).
      deadline: wall-clock budget in seconds (engine ``clock`` units) from
        submit to completion; enforced at decode-block boundaries — an
        expired request finalises TIMED_OUT with its accepted prefix.
        None = no deadline.
      queue_ttl: seconds the request may wait UNQUEUED work (queued or
        awaiting retry) before it is expired TIMED_OUT without ever
        decoding.  None = waits forever.
      priority: admission class — SMALLER is more urgent (0 = highest).
        Ignored by the default FIFO scheduler; with
        ``SchedulerPolicy.priority_admission`` it orders admission and
        (with ``preemption``) can evict strictly lower-priority slots.
      speculative_k: per-request speculative depth override (None =
        ``SchedulerPolicy.speculative_k``).  Explicit values must be in
        ``[1, max_new_tokens]`` — rejected otherwise.  Only greedy
        requests (temperature 0) speculate; see
        docs/serving.md §Speculative decoding.
      draft: per-request draft proposer name (None = policy
        ``speculative_draft``).  Must name a registered proposer usable
        on this engine's backend — rejected otherwise.
    """

    tokens: np.ndarray
    max_new_tokens: int
    temperature: float = 0.0
    top_k: int = 0
    eos_id: Optional[int] = None
    extras: Dict[str, np.ndarray] = dataclasses.field(default_factory=dict)
    deadline: Optional[float] = None
    queue_ttl: Optional[float] = None
    priority: int = 0
    speculative_k: Optional[int] = None
    draft: Optional[str] = None


def _next_pow2(n: int) -> int:
    """Smallest power of two >= n (compile-variant bucketing)."""
    return 1 << max(n - 1, 0).bit_length()


@dataclasses.dataclass
class _Slot:
    """Host-side bookkeeping for one cache slot."""

    rid: Optional[int] = None     # request id, None = free
    remaining: int = 0            # new-token budget left
    done: bool = False            # emitted eos (device went inactive)
    prefilling: bool = False      # reserved for an in-progress chunked prefill
    out: List[int] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class _Tracked:
    """Engine-side lifecycle record of one admitted request: the effective
    (possibly degraded) budget, deadline/TTL timestamps, and the
    retry-continuation state — ``accepted`` tokens survive a quarantine
    and are replayed as prompt suffix on re-prefill, so a greedy retry
    continues token-identically."""

    req: Request
    budget: int                       # post-degradation token budget
    submitted_at: float
    deadline_at: Optional[float]      # absolute; None = no deadline
    ttl_at: Optional[float]           # absolute queue-TTL; None = none
    degraded: bool = False
    chunk: Optional[int] = None       # per-request prefill-chunk override
    retries: int = 0
    accepted: List[int] = dataclasses.field(default_factory=list)
    not_before_block: int = 0         # retry backoff gate
    first_token_at: Optional[float] = None
    preemptions: int = 0
    # Preemption state handoff: the slot's decode state saved by
    # ``read_slot`` plus the token/pos vector entries — re-admission
    # splices it back and resumes WITHOUT re-prefill (token-identical by
    # construction).  Cleared when the request instead re-prefills (retry
    # path), where the saved state would be stale.
    saved_state: Any = None
    saved_token: int = 0
    saved_pos: int = 0
    admitted_at: Optional[float] = None

    def effective_tokens(self) -> np.ndarray:
        toks = np.asarray(self.req.tokens).reshape(-1).astype(np.int32)
        if self.accepted:
            return np.concatenate(
                [toks, np.asarray(self.accepted, np.int32)]
            )
        return toks


@dataclasses.dataclass
class _PartialPrefill:
    """An in-progress chunked admission: the request's prompt is being fed
    into a reserved slot's batch-1 cache one chunk per engine step, so
    decode blocks of the other slots interleave with long-prompt prefill."""

    rid: int
    slot: int
    caches: Any           # batch-1 cache pytree being accumulated
    consumed: int = 0     # prompt tokens absorbed so far
    logits: Optional[Array] = None  # last chunk's final-position logits
    last_chunk_block: int = 0       # interleave-ratio gate (decode_per_prefill)


class ServeEngine:
    """Continuous-batching inference engine over a slotted decode cache.

    Typical use::

        eng = ServeEngine(params, cfg, max_slots=8, n_max=4096)
        rid = eng.submit(Request(tokens=prompt, max_new_tokens=64))
        outputs = eng.run()          # {rid: np.ndarray of new tokens}
        results = eng.run(return_results=True)   # {rid: RequestResult}

    ``submit`` only enqueues; ``run`` (or repeated ``step``) drives
    admission and decoding until every request completes.  Prefill is
    jit-cached per (cfg, n_max) and re-traced per distinct prompt length —
    serve with bucketed prompt lengths if that matters.

    Resilience: ``policy=`` bounds the queue, degrades under overload and
    arms retry/quarantine; ``fault_plan=`` injects a seeded
    ``serve.faults.FaultPlan`` at the engine's boundaries (tests /
    ``benchmarks/bench_resilience.py``); ``stats()`` exposes the
    counters.  See docs/serving.md §Failure semantics.
    """

    def __init__(
        self,
        params,
        cfg: ModelConfig,
        max_slots: int,
        n_max: int,
        decode_block: int = 16,
        rng: Optional[Array] = None,
        cache_dtype=None,
        mesh=None,
        rules=None,
        prefill_chunk: Optional[int] = None,
        policy: Optional[ResiliencePolicy] = None,
        sched: Optional[SchedulerPolicy] = None,
        fault_plan=None,
        clock: Optional[Callable[[], float]] = None,
        state_dtype: str = "dense",
        kv_page_size: Optional[int] = None,
        kv_pages: Optional[int] = None,
    ):
        """Builds the engine and allocates the slotted cache.

        Args:
          params: model params from ``lm_init``.
          cfg: model config.
          max_slots: concurrent requests held on-device.
          n_max: per-slot context capacity (prompt + generated tokens) —
            bounds the KV cache on the softmax backend; the taylor moment
            state is O(1) regardless.
          decode_block: tokens advanced per device dispatch; admission
            happens at block boundaries, so this is also the continuous-
            batching granularity.
          rng: PRNG key for sampled decoding (defaults to PRNGKey(0)).
          cache_dtype: KV-cache dtype (defaults to ``cfg.dtype``).
          mesh: optional ``jax.sharding.Mesh`` (``make_serve_mesh``) — the
            engine runs end-to-end sharded: weights tensor-parallel via the
            training ``param_specs`` rules, the slot cache laid out by
            ``slot_cache_specs`` (slot axis over "data", heads/d_v over
            "model"), every cache-producing dispatch pinned + donated.  A
            1×1 mesh is the degenerate single-device engine; None (the
            default) skips the mesh machinery entirely.
          rules: logical→physical axis rules (default
            ``rules_for_mesh(mesh)``).
          prefill_chunk: when set, prompts longer than this are admitted
            via CHUNKED prefill — at most ``prefill_chunk`` prompt tokens
            per dispatch, interleaved with the decode blocks of in-flight
            slots, so one long prompt no longer stalls every other stream
            (decoder-only families; vlm/encdec fall back to whole-prompt
            prefill).  None = whole-prompt admission (the original
            behaviour).
          policy: ``ResiliencePolicy`` (None = defaults: unbounded queue,
            no degradation, health sweep every block, bounded retries).
          sched: ``SchedulerPolicy`` (None = defaults: strict-FIFO
            head-of-line admission, 1:1 decode/prefill interleave, fixed
            chunks, no preemption — the original scheduler exactly).
          fault_plan: optional ``serve.faults.FaultPlan`` consulted at
            block boundaries (deterministic fault injection).
          clock: monotonic-seconds source for deadlines/TTL (defaults to
            ``time.monotonic``; tests and the load harness inject virtual
            clocks — ``serve.load.VirtualClock``).
          state_dtype: slot-state storage dtype — "dense" (default) or a
            quantised moment representation ("int8"/"fp8", backends
            advertising it via ``state_dtypes``; the taylor backend's
            S1/S2 moments dominate per-slot bytes).  Compute always runs
            fp32-dense; only what the engine HOLDS between dispatches
            changes (docs/serving.md §Memory).
          kv_page_size: hold the KV slot cache PAGED with this pow2 page
            size (KV-kind backends advertising ``supports_paged_kv``) —
            per-slot page table, free-list allocator, live bytes
            proportional to tokens actually held rather than
            ``max_slots × n_max``.  Mutually exclusive with
            ``state_dtype``.
          kv_pages: paged-KV pool size in pages (default ``max_slots ×
            ⌈n_max / kv_page_size⌉`` — never exhausts).
        """
        if max_slots < 1 or decode_block < 1:
            raise ValueError("max_slots and decode_block must be >= 1")
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1 (or None)")
        self.cfg = cfg
        self.max_slots = max_slots
        self.n_max = n_max
        self.decode_block = decode_block
        self.prefill_chunk = prefill_chunk
        self.policy = policy if policy is not None else ResiliencePolicy()
        self.sched = sched if sched is not None else SchedulerPolicy()
        if self.sched.decode_per_prefill < 1:
            raise ValueError("decode_per_prefill must be >= 1")
        if self.sched.speculative_k < 0:
            raise ValueError("speculative_k must be >= 0 (0 = off)")
        if self.sched.speculative_k > 0:
            if not spec_mod.has_proposer(self.sched.speculative_draft):
                raise ValueError(
                    f"unknown speculative_draft "
                    f"{self.sched.speculative_draft!r}; registered: "
                    f"{spec_mod.proposer_names()}"
                )
            if not spec_mod.draft_available(cfg, self.sched.speculative_draft):
                raise ValueError(
                    f"draft {self.sched.speculative_draft!r} is not "
                    f"available on the {cfg.backend_desc!r} backend (no "
                    f"draft_config)"
                )
        self.fault_plan = fault_plan
        self._clock = clock if clock is not None else time.monotonic
        self.mesh = mesh
        dtype = jnp.dtype(cache_dtype or cfg.dtype)
        self._cache_dtype = dtype
        if mesh is not None:
            from repro.distributed import api as dist  # noqa: PLC0415
            from repro.distributed.sharding import (  # noqa: PLC0415
                named_shardings,
                param_specs,
            )

            self.rules = rules if rules is not None else dist.rules_for_mesh(mesh)
            pshapes = jax.eval_shape(lambda: params)
            pspecs = param_specs(pshapes, mesh, self.rules)
            self.params = jax.device_put(params, named_shardings(pspecs, mesh))
        else:
            self.rules = None
            self.params = params
        # The state store owns the slot cache's STORAGE representation
        # (dense / quantised moments / paged KV), its jitted slot ops and
        # — on a mesh — the stored-layout shardings every cache-producing
        # dispatch pins.  Validates state_dtype/kv_page_size against the
        # backend's capability flags (fail fast at construction).
        self.state_store = slots_mod.make_state_store(
            cfg, max_slots, n_max, dtype, mesh=mesh, rules=self.rules,
            state_dtype=state_dtype, kv_page_size=kv_page_size,
            kv_pages=kv_pages,
        )
        self._cache_ns = self.state_store.shardings
        self._write_slot = self.state_store.write_slot
        self._clear_slot = self.state_store.clear_slot
        self._read_slot = self.state_store.read_slot
        with self._device_ctx():
            self.caches = self.state_store.init_caches()
        self._scan_cache: Dict[Any, Any] = {}
        self._partial: Optional[_PartialPrefill] = None
        self._rng = rng if rng is not None else jax.random.PRNGKey(0)
        self._rid = itertools.count()
        self._queue: deque = deque()
        self._retry: List[int] = []       # rids waiting out a backoff
        self._requests: Dict[int, _Tracked] = {}
        self._results: Dict[int, RequestResult] = {}
        self._slots = [_Slot() for _ in range(max_slots)]
        self._block = 0                   # decode-block counter (1-based)
        self._stats: Counter = Counter()
        self._timings: Counter = Counter()  # host ns per phase (_phase)
        # Per-slot device-facing vectors (host copies are authoritative).
        self._token = np.zeros((max_slots,), np.int32)
        self._pos = np.zeros((max_slots,), np.int32)
        self._temp = np.zeros((max_slots,), np.float32)
        self._topk = np.zeros((max_slots,), np.int32)
        self._eos = np.full((max_slots,), -1, np.int32)
        self._spec = spec_mod.Speculator(self)

    # -- observability ------------------------------------------------------

    @contextlib.contextmanager
    def _phase(self, name: str, **args):
        """Times one engine phase and marks it in a profiler trace.

        Opens the ``serve.<name>`` trace annotation (``args`` become its
        arguments) and adds the phase's host time, by
        ``time.perf_counter_ns`` and not the injected ``clock`` (virtual
        under the load harness), and a count of one to ``timings()``.
        Always on; kept out of ``stats()``, which replays compare."""
        t = time.perf_counter_ns()
        try:
            with jax.profiler.TraceAnnotation(f"serve.{name}", **args):
                yield
        finally:
            self._timings[f"{name}_ns"] += time.perf_counter_ns() - t
            self._timings[f"{name}_n"] += 1

    # -- mesh helpers -------------------------------------------------------

    @contextlib.contextmanager
    def _device_ctx(self):
        """Mesh + sharding-rules context for every device dispatch (no-op on
        the single-device engine).  Tracing happens inside it, so the model
        layer's logical ``constrain`` annotations resolve."""
        if self.mesh is None:
            yield
        else:
            from repro.distributed import api as dist  # noqa: PLC0415

            with dist.sharding_rules(self.mesh, self.rules):
                yield

    def _decode_scan_fn(self, steps: int, sampling: bool, max_top_k: int):
        """Per-engine compiled decode_scan variants (the sharded builds pin
        this engine's cache shardings, so the global lru cache of
        ``engine.decode_scan`` cannot be shared)."""
        codec = self.state_store.jit_codec
        if self.mesh is None:
            return engine_mod._jitted_decode_scan(
                self.cfg, steps, sampling, max_top_k, codec
            )
        key = (steps, sampling, max_top_k)
        fn = self._scan_cache.get(key)
        if fn is None:
            fn = engine_mod.build_decode_scan(
                self.cfg, steps, sampling, max_top_k,
                cache_shardings=self._cache_ns, codec=codec,
            )
            self._scan_cache[key] = fn
        return fn

    def _prefill_chunk_fn(self):
        """The chunked-prefill dispatch: the global jit off-mesh; on a mesh
        a per-engine variant with the batch-1 cache output PINNED (same
        donation argument as the slot ops — an unpinned chunk would let
        the partitioner re-lay-out the carried cache every chunk)."""
        if self.mesh is None:
            return _jitted_prefill_chunk(self.cfg)
        fn = self._scan_cache.get("prefill_chunk")
        if fn is None:
            from jax.sharding import (  # noqa: PLC0415
                NamedSharding, PartitionSpec,
            )

            from repro.models.lm import lm_prefill_chunk  # noqa: PLC0415

            partial_ns = slots_mod.slot_cache_shardings(
                self.cfg, 1, self.n_max, self.mesh, self.rules,
                self._cache_dtype,
            )
            rep = NamedSharding(self.mesh, PartitionSpec())
            fn = jax.jit(
                functools.partial(lm_prefill_chunk, cfg=self.cfg),
                donate_argnums=(2,), out_shardings=(rep, partial_ns),
            )
            self._scan_cache["prefill_chunk"] = fn
        return fn

    def _corrupt_fn(self):
        """Fault-injection slot corruption (representation-aware; the
        store's mesh variant is pinned + donated, same argument as the
        slot ops)."""
        return self.state_store.corrupt_slot

    # -- submission ---------------------------------------------------------

    def _queue_depth(self) -> int:
        return len(self._queue) + len(self._retry)

    def submit(self, request: Request) -> int:
        """Validate, admission-control and enqueue a request.

        Returns the request id (key into ``run``'s result dict).  Invalid
        requests raise ``RequestRejected`` (a ``ValueError``) with a typed
        ``reason``; a full bounded queue sheds with ``QueueOverflow``.
        Either way the engine records a terminal ``REJECTED``
        ``RequestResult`` under ``exc.rid``.  Under overload
        (``degrade_queue_depth``) the request is admitted DEGRADED:
        budget clamped to ``degraded_max_new_tokens`` and chunked prefill
        forced via ``degrade_prefill_chunk``.
        """
        rid = next(self._rid)
        self._stats["submitted"] += 1
        try:
            self._validate(request)
            if (self.policy.max_queue is not None
                    and self._queue_depth() >= self.policy.max_queue):
                self._stats["shed"] += 1
                raise QueueOverflow(
                    f"queue full ({self._queue_depth()} >= max_queue="
                    f"{self.policy.max_queue}); request shed", rid=rid,
                )
        except RequestRejected as e:
            self._stats["rejected"] += 1
            now = self._clock()
            self._results[rid] = RequestResult(
                status=Status.REJECTED,
                tokens=np.zeros((0,), np.int32),
                error=str(e),
                submitted_at=now,
                finished_at=now,
            )
            if e.rid is None:
                e.rid = rid
            raise
        budget = request.max_new_tokens
        degraded = False
        chunk = None
        if (self.policy.degrade_queue_depth is not None
                and self._queue_depth() >= self.policy.degrade_queue_depth):
            degraded = True
            self._stats["degraded_admissions"] += 1
            if self.policy.degraded_max_new_tokens is not None:
                budget = min(budget, self.policy.degraded_max_new_tokens)
            chunk = self.policy.degrade_prefill_chunk
        now = self._clock()
        self._requests[rid] = _Tracked(
            req=request,
            budget=budget,
            submitted_at=now,
            deadline_at=(None if request.deadline is None
                         else now + request.deadline),
            ttl_at=(None if request.queue_ttl is None
                    else now + request.queue_ttl),
            degraded=degraded,
            chunk=chunk,
        )
        self._queue.append(rid)
        return rid

    def _validate(self, request: Request) -> None:
        """Typed submit-time validation (raises ``RequestRejected``)."""
        prompt_len = int(np.asarray(request.tokens).reshape(-1).shape[0])
        if prompt_len < 1:
            raise RequestRejected(
                "prompt is empty (need at least one token)",
                reason="empty_prompt",
            )
        if request.max_new_tokens < 1:
            raise RequestRejected(
                f"max_new_tokens must be >= 1, got {request.max_new_tokens}",
                reason="bad_budget",
            )
        if prompt_len > self.n_max:
            # Without this check the request is unadmittable and run()
            # spins forever waiting for a slot that can never prefill it.
            raise RequestRejected(
                f"prompt ({prompt_len} tokens) exceeds the engine's n_max "
                f"({self.n_max}); it can never be admitted",
                reason="prompt_too_long",
            )
        if prompt_len + request.max_new_tokens > self.n_max:
            raise RequestRejected(
                f"prompt ({prompt_len}) + max_new_tokens "
                f"({request.max_new_tokens}) exceeds n_max ({self.n_max})",
                reason="over_capacity",
            )
        # The slot cache preallocates kv_src/cross-KV leaves at the config's
        # source length, so every request's extras must match it exactly —
        # validate here rather than crash in write_slot mid-flight.
        expected = {}
        if self.cfg.family == "vlm":
            expected["image_embeds"] = (1, self.cfg.n_image_tokens,
                                        self.cfg.vision_dim)
        elif self.cfg.family == "encdec":
            expected["audio_frames"] = (1, self.cfg.n_audio_ctx,
                                        self.cfg.d_model)
        for name, shape in expected.items():
            got = tuple(np.asarray(request.extras.get(name, ())).shape)
            if got != shape:
                raise RequestRejected(
                    f"request extra {name!r} must have shape {shape} (the "
                    f"slot cache is preallocated from the config), got "
                    f"{got or 'missing'} — pad/resize the input to the "
                    f"configured source length",
                    reason="bad_extras",
                )
        # Speculative knobs (docs/serving.md §Speculative decoding): an
        # explicit per-request depth must be usable, and a draft name must
        # resolve in the proposer registry for THIS engine's backend.
        if request.speculative_k is not None:
            if request.speculative_k <= 0:
                raise RequestRejected(
                    f"speculative_k must be >= 1 when set, got "
                    f"{request.speculative_k} (omit it to disable "
                    f"speculation)",
                    reason="bad_speculative_k",
                )
            if request.speculative_k > request.max_new_tokens:
                raise RequestRejected(
                    f"speculative_k ({request.speculative_k}) exceeds "
                    f"max_new_tokens ({request.max_new_tokens}) — the "
                    f"draft window can never fit the budget",
                    reason="bad_speculative_k",
                )
        if request.draft is not None:
            if not spec_mod.has_proposer(request.draft):
                raise RequestRejected(
                    f"unknown draft proposer {request.draft!r}; "
                    f"registered: {spec_mod.proposer_names()}",
                    reason="unknown_draft",
                )
            if not spec_mod.draft_available(self.cfg, request.draft):
                raise RequestRejected(
                    f"draft {request.draft!r} is not available on the "
                    f"{self.cfg.backend_desc!r} backend (no draft_config)",
                    reason="draft_unavailable",
                )

    # -- terminal outcomes --------------------------------------------------

    def _finalize(self, rid: int, status: Status, tokens,
                  error: Optional[str] = None) -> None:
        """Record a request's terminal ``RequestResult`` and drop its
        tracking state (prompt + extras + saved preemption state must not
        accumulate)."""
        tr = self._requests.pop(rid, None)
        self._results[rid] = RequestResult(
            status=status,
            tokens=np.asarray(list(tokens), np.int32),
            error=error,
            retries=tr.retries if tr is not None else 0,
            preemptions=tr.preemptions if tr is not None else 0,
            submitted_at=tr.submitted_at if tr is not None else None,
            first_token_at=tr.first_token_at if tr is not None else None,
            finished_at=self._clock(),
            admitted_at=tr.admitted_at if tr is not None else None,
        )
        self._stats[status.value] += 1

    def _success_status(self, tr: Optional[_Tracked]) -> Status:
        return Status.DEGRADED if (tr is not None and tr.degraded) else Status.OK

    def _release_slot(self, idx: int) -> None:
        """Clear one slot's device state and free its host record."""
        with self._device_ctx():
            self.caches = self._clear_slot(
                self.caches, jnp.asarray(idx, jnp.int32)
            )
        self._slots[idx] = _Slot()
        self._spec.on_release(idx)

    def _requeue_for_retry(self, rid: int, accepted: List[int],
                           error: str) -> None:
        """Bounded retry-with-backoff after quarantine or dispatch loss.

        The accepted tokens are kept: re-admission prefills prompt +
        accepted and continues decoding from there, so a greedy retry is
        token-identical to an uninterrupted run.  Retries exhausted →
        FAILED with the accepted prefix."""
        tr = self._requests.get(rid)
        if tr is None:
            return
        if len(accepted) >= tr.budget:
            # everything was already produced — the loss cost nothing
            self._finalize(rid, self._success_status(tr), accepted)
            return
        if tr.retries >= self.policy.max_retries:
            self._finalize(rid, Status.FAILED, accepted, error=error)
            return
        tr.retries += 1
        self._stats["retries"] += 1
        tr.accepted = list(accepted)
        # The retry path re-prefills from prompt + accepted; any preemption
        # state saved earlier is older than ``accepted`` and must not be
        # resumed from.
        tr.saved_state = None
        tr.not_before_block = self._block + (
            self.policy.retry_backoff_blocks * (1 << (tr.retries - 1))
        )
        self._retry.append(rid)

    def _release_retries(self) -> None:
        """Move backoff-expired retries to the FRONT of the queue (they
        were already admitted once — retries jump the line)."""
        due = [rid for rid in self._retry
               if self._requests[rid].not_before_block <= self._block]
        if not due:
            return
        self._retry = [r for r in self._retry if r not in due]
        for rid in reversed(due):
            self._queue.appendleft(rid)

    def _expire(self, now: float) -> None:
        """Deadline / queue-TTL enforcement at a block boundary."""
        for rid in [r for r in self._queue]:
            tr = self._requests.get(rid)
            if tr is None:
                continue
            if ((tr.ttl_at is not None and now >= tr.ttl_at)
                    or (tr.deadline_at is not None and now >= tr.deadline_at)):
                self._queue.remove(rid)
                self._finalize(rid, Status.TIMED_OUT, tr.accepted,
                               error="expired while queued")
        for rid in list(self._retry):
            tr = self._requests.get(rid)
            if tr is None:
                continue
            if ((tr.ttl_at is not None and now >= tr.ttl_at)
                    or (tr.deadline_at is not None and now >= tr.deadline_at)):
                self._retry.remove(rid)
                self._finalize(rid, Status.TIMED_OUT, tr.accepted,
                               error="expired awaiting retry")
        for i, st in enumerate(self._slots):
            if st.rid is None:
                continue
            tr = self._requests.get(st.rid)
            if tr is None or tr.deadline_at is None or now < tr.deadline_at:
                continue
            if st.prefilling:
                if self._partial is not None and self._partial.rid == st.rid:
                    self._partial = None
            self._finalize(st.rid, Status.TIMED_OUT, st.out,
                           error="deadline exceeded mid-decode")
            self._release_slot(i)

    # -- slot lifecycle -----------------------------------------------------

    def _free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self._slots) if s.rid is None]

    def _active_mask(self) -> np.ndarray:
        return np.array(
            [s.rid is not None and not s.done and not s.prefilling
             and s.remaining > 0 for s in self._slots], bool,
        )

    def _install(self, slot: int, rid: int, tr: _Tracked, req_caches,
                 first: int, prompt_len: int) -> None:
        """Splice a fully-prefilled request into ``slot`` and arm it.

        For a retry continuation, ``prompt_len`` covers prompt + accepted
        tokens and the accepted prefix is replayed into the output."""
        req = tr.req
        with self._device_ctx():
            self.caches = self.state_store.ensure_tokens(
                self.caches, slot, prompt_len
            )
            self.caches = self._write_slot(
                self.caches, req_caches, jnp.asarray(slot, jnp.int32)
            )
        st = self._slots[slot]
        st.rid, st.done, st.prefilling = rid, False, False
        st.out = list(tr.accepted) + [first]
        st.remaining = tr.budget - len(st.out)
        if tr.first_token_at is None:
            tr.first_token_at = self._clock()
        tr.saved_state = None
        self._token[slot] = first
        self._pos[slot] = prompt_len
        self._temp[slot] = req.temperature
        self._topk[slot] = req.top_k
        self._eos[slot] = -1 if req.eos_id is None else req.eos_id
        if req.eos_id is not None and first == req.eos_id:
            st.done = True
        if not st.done and st.remaining > 0:
            self._spec.on_install(slot, tr, st.out)

    def _chunk_for(self, tr: _Tracked) -> Optional[int]:
        """Effective prefill-chunk size for one request, fattened by a
        power-of-two factor when the queue is deep (``fat_chunk_depth``):
        the measured chunked-prefill cost is per-DISPATCH, so a backlog is
        drained fastest with fewer, fatter chunks.  Power-of-two bucketing
        keeps the number of compiled chunk widths O(log)."""
        chunk = tr.chunk if tr.chunk is not None else self.prefill_chunk
        depth_at = self.sched.fat_chunk_depth
        if chunk is None or not depth_at:
            return chunk
        depth = self._queue_depth()
        if depth < depth_at:
            return chunk
        factor = min(self.sched.fat_chunk_max,
                     _next_pow2(1 + depth // depth_at))
        return chunk * factor

    def _needs_chunked_prefill(self, tr: _Tracked) -> bool:
        chunk = self._chunk_for(tr)
        return (
            chunk is not None
            and self.cfg.family == "lm"
            and not tr.req.extras
            and tr.effective_tokens().shape[-1] > chunk
        )

    def _advance_partial(self) -> None:
        """Feed ONE more prompt chunk of the in-progress chunked admission;
        finalize (sample first token + write_slot) when the prompt is
        fully absorbed."""
        p = self._partial
        tr = self._requests[p.rid]
        req = tr.req
        with self._phase("prefill_chunk"):
            toks = tr.effective_tokens()
            n = int(toks.shape[-1])
            take = min(self._chunk_for(tr), n - p.consumed)
            chunk = jnp.asarray(toks[None, p.consumed : p.consumed + take],
                                jnp.int32)
            with self._device_ctx():
                p.logits, p.caches = self._prefill_chunk_fn()(
                    self.params, chunk, p.caches,
                    jnp.asarray(p.consumed, jnp.int32),
                )
            self._stats["dispatches"] += 1
            self._stats["prefill_dispatches"] += 1
            self._stats["prefill_tokens"] += take
            p.consumed += take
            p.last_chunk_block = self._block
            if p.consumed < n:
                return
            self._rng, sub = jax.random.split(self._rng)
            first = int(np.asarray(sample_tokens(
                p.logits, sub,
                jnp.asarray([req.temperature], jnp.float32),
                jnp.asarray([req.top_k], jnp.int32),
                max_top_k=req.top_k,
            ))[0])
        self._install(p.slot, p.rid, tr, p.caches, first, n)
        self._partial = None

    def _partial_due(self) -> bool:
        """Interleave-ratio gate: is the in-flight chunked admission owed
        its next chunk this step?  With ``decode_per_prefill = N`` a chunk
        feeds every N-th engine step while decode is active; an otherwise
        idle engine always feeds (throttling it would be pure waste)."""
        n = self.sched.decode_per_prefill
        if n <= 1 or not self._active_mask().any():
            return True
        return self._block - self._partial.last_chunk_block >= n

    def _admission_order(self) -> List[int]:
        """Queued rids in admission order: arrival order (FIFO, with
        retries already at the queue front), or stable
        ``(priority, queue position)`` under ``priority_admission``."""
        if not self.sched.priority_admission:
            return list(self._queue)
        return [rid for _, _, rid in sorted(
            (self._requests[rid].req.priority, i, rid)
            for i, rid in enumerate(self._queue)
        )]

    def _resume(self, slot: int, rid: int, tr: _Tracked) -> None:
        """Re-admit a preempted request from its saved decode state.

        The state handoff: ``write_slot`` splices the ``read_slot``
        snapshot back in and the token/pos vector entries are restored, so
        decoding continues from EXACTLY the preempted step — no prefill
        dispatch, token-identical by construction (tested)."""
        req = tr.req
        with self._device_ctx():
            self.caches = self.state_store.ensure_tokens(
                self.caches, slot, int(tr.saved_pos)
            )
            self.caches = self._write_slot(
                self.caches, tr.saved_state, jnp.asarray(slot, jnp.int32)
            )
        st = self._slots[slot]
        st.rid, st.done, st.prefilling = rid, False, False
        st.out = list(tr.accepted)
        st.remaining = tr.budget - len(st.out)
        self._token[slot] = tr.saved_token
        self._pos[slot] = tr.saved_pos
        self._temp[slot] = req.temperature
        self._topk[slot] = req.top_k
        self._eos[slot] = -1 if req.eos_id is None else req.eos_id
        tr.saved_state = None
        self._stats["resumes"] += 1
        self._spec.on_resume(slot, tr)

    def _preempt(self) -> None:
        """Evict at most one over-budget low-priority slot per block.

        Fires only when preemption is on, no slot is free, and a STRICTLY
        higher-priority request is queued.  The victim — worst admission
        class first, most remaining budget as tie-break — has its decode
        state saved via ``read_slot`` (O(1) bytes on the taylor backend)
        and re-enters the queue; ``_resume`` later splices the state back.
        ``max_preemptions`` bounds how often one request can be bounced."""
        if not (self.sched.preemption and self._queue):
            return
        if any(s.rid is None for s in self._slots):
            return
        best_wait = min(self._requests[rid].req.priority
                        for rid in self._queue if rid in self._requests)
        victim = None
        for i, st in enumerate(self._slots):
            if (st.rid is None or st.prefilling or st.done
                    or st.remaining <= 0):
                continue
            tr = self._requests.get(st.rid)
            if (tr is None or tr.req.priority <= best_wait
                    or tr.preemptions >= self.sched.max_preemptions
                    or len(st.out) < self.sched.preempt_min_tokens):
                continue
            key = (tr.req.priority, st.remaining, st.rid)
            if victim is None or key > victim[0]:
                victim = (key, i)
        if victim is None:
            return
        i = victim[1]
        st = self._slots[i]
        rid, tr = st.rid, self._requests[st.rid]
        with self._device_ctx():
            tr.saved_state = self._read_slot(
                self.caches, jnp.asarray(i, jnp.int32)
            )
        tr.saved_token = int(self._token[i])
        tr.saved_pos = int(self._pos[i])
        tr.accepted = list(st.out)
        tr.preemptions += 1
        self._stats["preemptions"] += 1
        self._release_slot(i)
        self._queue.append(rid)

    def _admit(self) -> None:
        """Prefill queued requests into free slots (between decode blocks).

        Admission-order requests with equal prompt length share ONE
        batched prefill dispatch (their per-request caches are sliced out
        with ``read_slot`` and spliced into slots), so a burst of
        same-shape requests — e.g. everything ``generate`` submits — pays
        one prefill, not one per request.  Under the default FIFO policy
        only CONSECUTIVE equal-length requests group (strict arrival
        order); ``priority_admission`` groups equal lengths from anywhere
        in the admission order (fewer, fatter dispatches).  Preempted
        requests resume from their saved state with NO dispatch at all.

        With ``prefill_chunk`` set, a long prompt is admitted CHUNK BY
        CHUNK: its slot is reserved, chunks are prefilled per the
        ``decode_per_prefill`` interleave ratio, and decode blocks of the
        other slots run in between.  Under FIFO, later requests wait
        behind the long prompt (head-of-line, the original contract);
        under ``priority_admission`` they keep admitting into remaining
        free slots — the fairness fix ``tests/test_load.py`` pins."""
        # Advance an in-progress chunked admission (unless the fault plan
        # stalls it, or the interleave ratio says decode blocks go first).
        if self._partial is not None:
            if (self.fault_plan is not None
                    and self.fault_plan.prefill_stalled(self._block)):
                self._stats["prefill_stalls"] += 1
            elif self._partial_due():
                self._advance_partial()
        if self._partial is not None and not self.sched.priority_admission:
            return  # strict FIFO: nothing admits behind an in-flight prefill
        free = self._free_slots()
        order = self._admission_order()
        while free and order:
            rid = order[0]
            tr = self._requests[rid]
            if tr.saved_state is not None:
                order.pop(0)
                self._queue.remove(rid)
                self._resume(free.pop(0), rid, tr)
                continue
            if self._needs_chunked_prefill(tr):
                if self._partial is not None:
                    # one partial at a time; under priority admission the
                    # rest of the order may still admit into other slots
                    order.pop(0)
                    continue
                order.pop(0)
                self._queue.remove(rid)
                slot = free.pop(0)
                st = self._slots[slot]
                st.rid, st.prefilling, st.done = rid, True, False
                st.remaining, st.out = 0, []
                if tr.admitted_at is None:
                    tr.admitted_at = self._clock()
                with self._device_ctx():
                    partial_caches = slots_mod.init_slot_caches(
                        self.cfg, 1, self.n_max, self._cache_dtype,
                        mesh=self.mesh, rules=self.rules,
                    )
                self._partial = _PartialPrefill(
                    rid=rid, slot=slot, caches=partial_caches,
                    last_chunk_block=self._block,
                )
                self._advance_partial()  # first chunk this step
                if not self.sched.priority_admission:
                    return  # FIFO: later requests wait behind the long prompt
                continue
            # Batched admission group: equal-effective-length requests in
            # admission order (extras shapes are uniform per config —
            # enforced at submit).  FIFO stops at the first mismatch to
            # preserve strict arrival order; priority admission scans on.
            group = [rid]
            glen = tr.effective_tokens().shape[-1]
            for cand in order[1:]:
                if len(group) >= len(free):
                    break
                ctr = self._requests[cand]
                if (ctr.saved_state is None
                        and not self._needs_chunked_prefill(ctr)
                        and ctr.effective_tokens().shape[-1] == glen):
                    group.append(cand)
                elif not self.sched.priority_admission:
                    break
            order = [r for r in order if r not in group]
            for g in group:
                self._queue.remove(g)
            trs = [self._requests[g] for g in group]
            now = self._clock()
            for t in trs:
                if t.admitted_at is None:
                    t.admitted_at = now
            with self._phase("prefill", group=len(group), length=int(glen)):
                batch = {"tokens": jnp.asarray(
                    np.stack([t.effective_tokens() for t in trs]), jnp.int32
                )}
                for k in trs[0].req.extras:
                    batch[k] = jnp.asarray(
                        np.concatenate([np.asarray(t.req.extras[k])
                                        for t in trs])
                    )
                with self._device_ctx():
                    logits, pref_caches = _jitted_prefill(
                        self.cfg, self.n_max
                    )(self.params, batch)
                self._stats["dispatches"] += 1
                self._stats["prefill_dispatches"] += 1
                self._stats["prefill_tokens"] += int(glen) * len(group)
                self._rng, sub = jax.random.split(self._rng)
                temps = jnp.asarray([t.req.temperature for t in trs],
                                    jnp.float32)
                topks = jnp.asarray([t.req.top_k for t in trs], jnp.int32)
                firsts = np.asarray(sample_tokens(
                    logits, sub, temps, topks,
                    max_top_k=max(t.req.top_k for t in trs),
                ))
            for j, (g, t) in enumerate(zip(group, trs)):
                slot = free.pop(0)
                with self._device_ctx():
                    # pref_caches is the DENSE batched prefill output —
                    # slice with the dense read, not the store's
                    # (representation-decoding) read_slot.
                    req_caches = (
                        pref_caches if len(group) == 1
                        else self.state_store.read_dense(
                            pref_caches, jnp.asarray(j, jnp.int32)
                        )
                    )
                self._install(slot, g, t, req_caches, int(firsts[j]),
                              int(glen))

    def _retire_finished(self) -> None:
        for i, st in enumerate(self._slots):
            if st.prefilling:
                continue  # reserved for an in-progress chunked admission
            if st.rid is not None and (st.done or st.remaining <= 0):
                tr = self._requests.get(st.rid)
                self._finalize(st.rid, self._success_status(tr), st.out)
                self._release_slot(i)

    # -- fault handling -----------------------------------------------------

    def _dispatch(self, scan_fn, args):
        """One decode-block dispatch with bounded in-place retries.

        The fault plan's injected failure fires BEFORE the real dispatch,
        so the donated cache survives and an in-place retry is safe and
        token-identical.  A real dispatch failure may have consumed the
        donated buffers — retry only while every cache leaf is alive;
        otherwise (or past ``max_dispatch_retries``) the exception
        propagates to ``step``'s rebuild path."""
        attempts = 0
        while True:
            try:
                if self.fault_plan is not None:
                    self.fault_plan.check_dispatch(self._block)
                with self._device_ctx():
                    return scan_fn(*args)
            except Exception:
                self._stats["dispatch_failures"] += 1
                attempts += 1
                alive = not any(
                    getattr(leaf, "is_deleted", lambda: False)()
                    for leaf in jax.tree_util.tree_leaves(self.caches)
                )
                if attempts <= self.policy.max_dispatch_retries and alive:
                    self._stats["dispatch_retries"] += 1
                    continue
                raise

    def _rebuild_after_loss(self, error: str) -> None:
        """Recover from an unretryable dispatch failure: finalize slots
        whose output was already complete, requeue live ones (bounded
        retries — their accepted tokens are replayed on re-prefill), and
        rebuild the slotted cache from zeros."""
        self._stats["cache_rebuilds"] += 1
        if self._partial is not None:
            p, self._partial = self._partial, None
            self._requeue_for_retry(p.rid, [], error)
        for i, st in enumerate(self._slots):
            if st.rid is None:
                continue
            if st.done or (st.remaining <= 0 and not st.prefilling):
                tr = self._requests.get(st.rid)
                self._finalize(st.rid, self._success_status(tr), st.out)
            elif not st.prefilling:
                self._requeue_for_retry(st.rid, list(st.out), error)
            self._slots[i] = _Slot()
        with self._device_ctx():
            # Also resets the page allocator: every page returns to the
            # free list alongside the re-zeroed pools.
            self.caches = self.state_store.init_caches()
        self._token[:] = 0
        self._pos[:] = 0
        self._temp[:] = 0.0
        self._topk[:] = 0
        self._eos[:] = -1
        self._spec.on_rebuild()

    def _inject_corruptions(self) -> None:
        """Apply due ``SlotCorruption`` events (fault plan) to the live
        cache — AFTER this block's tokens were consumed, so the poisoned
        state has not yet produced a trusted token."""
        if self.fault_plan is None:
            return
        for e in self.fault_plan.take_corruptions(self._block):
            if not 0 <= e.slot < self.max_slots:
                continue
            fill = float("nan") if e.mode == "nan" else float("inf")
            with self._device_ctx():
                self.caches = self._corrupt_fn()(
                    self.caches, jnp.asarray(e.slot, jnp.int32),
                    jnp.asarray(fill, jnp.float32),
                )
            self._stats["corruptions_injected"] += 1

    def _health_sweep(self) -> None:
        """Quarantine slots whose decode state went non-finite.

        Runs every ``health_check_every`` blocks, straight after the
        decode block (and any injected corruption), so a poisoned slot is
        caught before ANY of its garbage tokens is accepted.  Live slots
        are quarantined (cleared + requeued with their accepted prefix);
        free/retired/prefilling slots are just scrubbed — their region of
        the cache is dead state that ``write_slot`` fully overwrites on
        admission.  Co-batched slots are untouched (tested)."""
        every = self.policy.health_check_every
        if not every or self._block % every:
            return
        occupied = any(s.rid is not None for s in self._slots)
        if not occupied:
            return
        with self._phase("health"), self._device_ctx():
            health = np.asarray(self.state_store.health(self.caches))
        self._stats["health_checks"] += 1
        if health.all():
            return
        for i in np.flatnonzero(~health):
            i = int(i)
            st = self._slots[i]
            live = (st.rid is not None and not st.prefilling
                    and not st.done and st.remaining > 0)
            finished = (st.rid is not None and not st.prefilling
                        and (st.done or st.remaining <= 0))
            if live:
                self._stats["quarantined"] += 1
                rid, out = st.rid, list(st.out)
                self._slots[i] = _Slot()
                self._spec.on_release(i)
                self._requeue_for_retry(
                    rid, out, "slot state corrupted (quarantined)"
                )
            elif finished:
                # output completed before the corruption — finalize as
                # success; only the dead cache region was poisoned
                tr = self._requests.get(st.rid)
                self._finalize(st.rid, self._success_status(tr), st.out)
                self._slots[i] = _Slot()
                self._spec.on_release(i)
            # prefilling slots keep their reservation: the partial's
            # batch-1 caches live outside the slot cache
            with self._device_ctx():
                self.caches = self._clear_slot(
                    self.caches, jnp.asarray(i, jnp.int32)
                )

    def _has_work(self) -> bool:
        return (bool(self._queue) or bool(self._retry)
                or any(s.rid is not None for s in self._slots))

    # -- decoding -----------------------------------------------------------

    def step(self) -> bool:
        """Admit + advance one decode block.  Returns True while work remains.

        One call = at most one ``decode_scan`` dispatch, preceded by the
        block-boundary bookkeeping in a fixed order: fault-plan floods →
        deadline/TTL expiry → retire → release backoff retries → admit →
        dispatch (with bounded retry / cache rebuild) → corruption
        injection → health sweep → retire.  Exposed for tests and for
        callers interleaving submission with decoding; ``run`` loops it.
        Timed as the ``step`` phase (``timings()``).
        """
        with self._phase("step"):
            return self._step()

    def _step(self) -> bool:
        self._block += 1
        now = self._clock()
        if self.fault_plan is not None:
            for req in self.fault_plan.flood_requests(self._block,
                                                      self.cfg.vocab):
                try:
                    self.submit(req)
                except RequestRejected:
                    pass  # shed/rejected floods are terminal via _results
        self._expire(now)
        self._retire_finished()
        self._release_retries()
        self._preempt()
        with self._phase("admit"):
            self._admit()
        # Speculative rounds run BEFORE the decode block: due greedy slots
        # draft + verify (one chunked dispatch per depth) and are excluded
        # from this block's active mask — the decode scan preserves
        # inactive slots' state bit-identically, so speculative and plain
        # slots co-batch without interference.
        spec_handled = self._spec.run_rounds()
        active = self._active_mask()
        for i in spec_handled:
            active[i] = False
        if not active.any():
            if spec_handled:
                # All live work advanced via verify this step — the
                # corruption/health machinery must still run at the block
                # boundary (quarantine of speculating slots is tested).
                self._inject_corruptions()
                self._health_sweep()
            self._retire_finished()
            return self._has_work()
        with self._phase("decode"):
            steps = min(
                self.decode_block,
                max(s.remaining for s in self._slots
                    if s.rid is not None and not s.done and not s.prefilling),
            )
            # steps and max_top_k are static jit keys: bucket both to powers
            # of two so the number of compiled full-model scan variants
            # stays O(log) in the values clients supply, not O(distinct
            # values).  Over-decoding a few tokens past the smallest budget
            # is harmless — the host trims and retired slots freeze.
            steps = min(self.decode_block, _next_pow2(max(steps, 1)))
            # Static specialization for the compiled scan: all-greedy
            # batches (the common case) skip sampling entirely, and top-k is
            # bounded by the largest k among occupied slots.
            occupied = [i for i, s in enumerate(self._slots)
                        if s.rid is not None and not s.prefilling]
            sampling = any(self._temp[i] > 0 for i in occupied)
            max_top_k = int(max((self._topk[i] for i in occupied),
                                default=0))
            max_top_k = _next_pow2(max_top_k) if max_top_k > 0 else 0
            self._rng, sub = jax.random.split(self._rng)
            if self.state_store.paged:
                # Every active slot writes up to ``steps`` new KV rows this
                # dispatch — grow its page prefix first (host-side table,
                # pushed once if anything changed).
                for i in np.flatnonzero(active):
                    self.caches = self.state_store.ensure_tokens(
                        self.caches, int(i), int(self._pos[i]) + int(steps)
                    )
            scan_fn = self._decode_scan_fn(int(steps), bool(sampling),
                                           max_top_k)
            try:
                (self.caches, token, pos, dev_active, _, toks, mask) = (
                    self._dispatch(scan_fn, (
                        self.params,
                        self.caches,
                        jnp.asarray(self._token),
                        jnp.asarray(self._pos),
                        jnp.asarray(active),
                        jnp.asarray(self._temp),
                        jnp.asarray(self._topk),
                        jnp.asarray(self._eos),
                        sub,
                    ))
                )
            except Exception as e:  # noqa: BLE001 — resilience boundary
                self._rebuild_after_loss(f"decode dispatch failed: {e}")
                return self._has_work()
            self._stats["dispatches"] += 1
            self._stats["decode_dispatches"] += 1
            toks = np.asarray(toks)
            mask = np.asarray(mask)
        # np.array (copy): np.asarray of a jax array is a read-only view,
        # and _admit writes these in place.
        self._token = np.array(token, np.int32)
        self._pos = np.array(pos, np.int32)
        dev_active = np.asarray(dev_active)
        for i, st in enumerate(self._slots):
            if st.rid is None or st.done or st.prefilling:
                continue
            if not active[i]:
                continue
            emitted_from = len(st.out)
            for t in range(toks.shape[0]):
                if not mask[t, i] or st.remaining <= 0:
                    break
                st.out.append(int(toks[t, i]))
                st.remaining -= 1
                self._stats["decode_tokens"] += 1
                if self._eos[i] >= 0 and toks[t, i] == self._eos[i]:
                    st.done = True
                    break
            # A speculating slot decodes its final <= k tokens plainly —
            # keep its host-side draft context in sync.
            self._spec.on_decode_tokens(i, st.out[emitted_from:])
            if not dev_active[i]:
                st.done = True
        self._inject_corruptions()
        self._health_sweep()
        self._retire_finished()
        return self._has_work()

    def run(self, return_results: bool = False):
        """Drive admission + decoding until every submitted request is done.

        Drains the finished-result buffer: each request's outcome is
        returned by exactly one ``run`` call (a long-lived engine must not
        accumulate every answer it ever produced).

        Args:
          return_results: False (default) returns ``{rid: np.ndarray}`` of
            new tokens — the pre-resilience contract (non-OK statuses
            appear with their accepted-prefix tokens).  True returns
            ``{rid: RequestResult}`` with the full terminal status.

        Returns:
          ``{rid: np.ndarray[int32]}`` or ``{rid: RequestResult}`` for
          every request that reached a terminal status since the previous
          ``run`` (including REJECTED submissions recorded via their
          exception's ``rid``).
        """
        while self.step():
            pass
        return self.poll() if return_results else {
            rid: r.tokens for rid, r in self.poll().items()
        }

    def poll(self) -> Dict[int, RequestResult]:
        """Drain terminal results accumulated so far WITHOUT stepping.

        For callers driving the engine step-by-step (the load harness,
        tests interleaving submission with decoding): each terminal
        ``RequestResult`` is returned by exactly one ``poll``/``run`` call.

        Returns:
          ``{rid: RequestResult}`` for every request that reached a
          terminal status since the previous drain (possibly empty).
        """
        out, self._results = self._results, {}
        return out

    # -- introspection ------------------------------------------------------

    def stats(self) -> Dict[str, int]:
        """Engine counters + gauges (monotonic since construction).

        Counters: ``submitted``, ``rejected``, ``shed``,
        ``degraded_admissions``, terminal statuses (``ok``, ``degraded``,
        ``timed_out``, ``failed``), ``quarantined``, ``retries``,
        ``dispatch_failures``, ``dispatch_retries``, ``cache_rebuilds``,
        ``corruptions_injected``, ``health_checks``, ``prefill_stalls``;
        dispatch accounting: ``dispatches`` (every device round-trip),
        ``decode_dispatches``/``decode_tokens`` and
        ``prefill_dispatches``/``prefill_tokens`` (the
        dispatches-per-token numerator/denominators ``bench_load``
        reports); scheduling: ``preemptions``, ``resumes``.
        Speculative decoding (docs/serving.md §Speculative decoding):
        ``spec_rounds``/``verify_dispatches`` (verify chunk dispatches),
        ``verify_tokens`` (window tokens absorbed, including rollback
        re-absorbs), ``spec_tokens`` (tokens EMITTED via verify — the
        extra ``dispatches_per_token`` denominator next to
        ``decode_tokens``), ``spec_drafted``/``spec_accepted`` (the
        acceptance-rate ratio), ``spec_full_accepts``,
        ``spec_rollbacks``, and ``draft_dispatches``/``draft_tokens``
        (order-1 self-draft cost; the n-gram proposer is host-side and
        adds none).
        Gauges: ``blocks`` (decode-block counter), ``queue_depth``
        (queued + awaiting retry), ``slots_occupied``.

        Returns:
          Dict of counter/gauge name to int value (absent counter = 0).
        """
        out = dict(self._stats)
        out["blocks"] = self._block
        out["queue_depth"] = self._queue_depth()
        out["slots_occupied"] = sum(
            1 for s in self._slots if s.rid is not None
        )
        return out

    def timings(self) -> Dict[str, int]:
        """Host time and count of each engine phase since construction.

        For every phase of ``PHASES``: ``<phase>_ns``, its summed host time
        by ``time.perf_counter_ns`` (wall clock even under an injected
        virtual ``clock``), and ``<phase>_n``, how often it ran.  Phases,
        nested as listed, each also a ``serve.<phase>`` trace annotation:
        ``step`` (all of ``step()``) holds ``admit`` (admission, which
        holds ``prefill``, one batched whole-prompt prefill up to its first
        tokens on the host, and ``prefill_chunk``, one chunk of a chunked
        admission), ``decode`` (one decode block, from building its
        arguments to its tokens on the host) and ``health`` (one
        ``state_health`` sweep).  Kept apart from ``stats()``, whose
        counters replays compare for equality (docs/serving.md
        §Observability).

        Returns:
          Dict of ``<phase>_ns`` / ``<phase>_n`` to int (0 for a phase that
          never ran).
        """
        return {f"{p}_{u}": self._timings[f"{p}_{u}"]
                for p in PHASES for u in ("ns", "n")}

    @property
    def slot_state_bytes(self) -> int:
        """Decode-state bytes one slot occupies (memory per admission).

        Representation-aware LIVE accounting: the paged KV store counts
        pages in use, not pool capacity, and the quantised stores count
        the compressed payload + scales.  Dense state reproduces the
        historical total-bytes / max_slots number exactly (regression-
        pinned in tests/test_paged_kv.py)."""
        return self.state_store.slot_bytes(self.caches)

    @property
    def live_state_bytes(self) -> int:
        """Total decode-state bytes currently LIVE on device (the sum
        ``slot_state_bytes`` averages; varies block to block for the
        paged KV store as slots grow and release pages)."""
        return self.state_store.live_bytes(self.caches)
