"""Continuous-batching serving engine on per-slot caches (KV ring or SSM
state — any architecture :func:`repro.models.transformer.supports_slot_serving`
admits).

The engine owns two jitted steps built by :mod:`repro.launch.step_fns`:

* a cache-writing **prefill** step (one compilation per prompt bucket
  length × {fresh, resume}; one call per prompt CHUNK) that runs the chunk
  as a single row, splices the finished row into the request's slot, and —
  on the final chunk — emits the request's first token, sampled by the
  request's seeded sampler (greedy by default) — while in-flight decode
  state in every other slot passes through untouched;
* a slot-aware **decode** step (compiled once) that advances every busy
  slot by one token per tick, sampling inside the jitted step;
* when requests opt into speculative decoding (``Request.spec``), a
  slot-aware **verify** step (compiled once per draft budget) that scores
  each slot's draft proposals in one pass and advances every busy slot by
  the accepted length — up to k+1 tokens per tick, streams bit-identical
  to plain decoding, rejected drafts rolled back leaving no cache residue
  (see :mod:`repro.serving.speculative` and docs/speculative.md).

Prompts longer than ``prefill_chunk`` are split into fixed-size chunks fed
one per tick, interleaved with in-flight decode — a long prompt occupies
one slot while admitting instead of stalling the whole engine. Chunking is
a pure function of the prompt length and the engine constants, never of
scheduling, so continuous and static runs chunk identically and token
streams stay bit-identical across policies. Recurrent-state (mamba/rwkv)
slots ride the same machinery: their prefill checkpoints the carry at the
true prompt length (pads leave it bit-unchanged), and the decode step
merges inactive rows' states back so a prefilling neighbor slot is never
disturbed.

Because a slot is freed by resetting its per-row position counter (and
zeroing recurrent rows), a finished request's slot is re-admissible on the
very next tick with no re-jitting and no device reallocation — the property
that makes continuous batching beat the static loop: the static policy
holds all ``n_slots`` rows hostage until the batch's LONGEST request
finishes, decoding mostly padding near the end, while the engine refills
each slot the tick it frees.

Time runs on two clocks: *ticks* (one loop iteration; arrival staggering
and TTFT/latency are measured in ticks, deterministically) and wall seconds
(throughput). ``run(..., static=True)`` executes the batch-synchronous
reference policy through the SAME jitted steps, which is what makes the
benchmark comparison and the bit-identity regression test meaningful.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ParallelConfig, ShapeSuite
from repro.launch import step_fns
from repro.models import transformer as tf
from repro.obs.trace import span
from repro.serving import sampling
from repro.serving.prefix import PrefixCache
from repro.serving.request import Request, RequestState
from repro.serving.scheduler import SlotScheduler
from repro.serving.slo import slo_report
from repro.serving.speculative import (AdaptiveDraftController, NgramDrafter,
                                       SpecParams, drafter_label)
from repro.serving.telemetry import (STATS_FIELDS, TelemetryLog,
                                     stats_vector)


# The engine's own device programs take their names from these functions:
# the device trace shows ``jit_reset_slots``, ``jit_extract_row`` and (from
# ``tf.adopt_prefix`` itself) ``jit_adopt_prefix``.
def reset_slots(caches, free_mask):
    return tf.reset_cache_slots(caches, free_mask)


def extract_row(caches, slot):
    return tf.extract_cache_row(caches, slot)


def make_reset(cache_sharding):
    """The slot-reset program. Its output is pinned to the caches'
    sharding: on multi-device meshes a free-layout reset would let GSPMD
    re-shard a leaf and the next prefill/decode call would reject its own
    cache. The caches are donated, as the steps donate theirs, so a reset
    writes no second copy of them."""
    return jax.jit(reset_slots, out_shardings=cache_sharding,
                   donate_argnums=(0,))


def _pow2_at_least(n: int, floor: int) -> int:
    b = max(floor, 1)
    while b < n:
        b *= 2
    return b


class ServingEngine:
    """Continuous-batching decode engine for one data-parallel replica.

    ``n_slots`` is the cache batch (concurrent requests); ``max_len`` the
    per-slot ring-cache length. ``prefill_chunk`` bounds how much prompt
    one prefill call writes (default: the largest single call the cache
    geometry allows); longer prompts stream in chunk-per-tick.
    ``stats_reducer`` (see :func:`repro.serving.telemetry.make_stats_reducer`)
    sums per-tick stats across replicas with the b=1 dual-root tree;
    None = single replica.

    ``drafter`` serves requests that opt into speculative decoding via
    ``Request.spec`` (a :class:`~repro.serving.speculative.SpecParams`):
    each such tick proposes up to k draft tokens per slot and verifies all
    of them in ONE jitted pass (:func:`repro.launch.step_fns
    .make_verify_step`) — emitting several tokens per b=1-reduction tick
    with streams bit-identical to plain decoding. Default: a
    :class:`~repro.serving.speculative.NgramDrafter` (prompt lookup, no
    second model); pass a
    :class:`~repro.serving.speculative.DraftModelDrafter` built on this
    engine's mesh and ``n_slots`` to draft with a smaller model.

    ``draft_headroom`` widens window/chunk-bounded attention rings by that
    many slots (see ``init_cache(ring_slack=...)``): a k-draft verify call
    writes k+1 tokens at once, and without the slack its later writes would
    wrap a window-sized ring over positions the call's earliest queries
    still need — sequential decode never hits this, so the headroom is what
    keeps speculative verification bit-identical on SWA/chunked-attention
    architectures. Full-attention rings are never widened. Requests may
    speculate up to ``draft_k == draft_headroom`` on bounded-ring configs.
    The default matches ``SpecParams().draft_k`` — default speculation
    works out of the box at a few extra ring slots per bounded layer; set
    0 to reclaim them on engines that never speculate, or raise it (up to
    ``MAX_DRAFT_K``) for wider draft budgets.

    ``prefix_cache=True`` turns on cross-request prefix caching
    (:mod:`repro.serving.prefix`, docs/prefix_caching.md): each session
    keeps a trie of slot-cache rows snapshotted at prefill-chunk-grid
    boundaries, and an admission whose history shares a cached boundary
    prefix adopts that row (one jitted row copy) and prefills only from
    the first divergent chunk — warm-prefix TTFT collapses to ~1 tick.
    Streams stay bit-identical to cold prefill under every policy: rows
    are pure functions of the tokens that produced them, and adoption
    lands on the same chunk grid cold admission would have used.
    ``prefix_cache_nodes`` bounds the trie (LRU eviction; nodes pinned by
    in-flight admissions are never evicted). When prefix caching is on
    and the drafter is the default :class:`NgramDrafter`, the trie also
    serves as its shared n-gram corpus.
    """

    def __init__(self, cfg, pcfg: ParallelConfig, mesh, params, *,
                 n_slots: int = 4, max_len: int = 128,
                 min_prefill_bucket: int = 16, prefill_chunk: int | None = None,
                 stats_reducer=None, drafter=None,
                 draft_headroom: int | None = None,
                 prefix_cache: bool = False, prefix_cache_nodes: int = 256,
                 tracer=None, metrics=None, metrics_every: int = 0,
                 metrics_sink=None):
        if not tf.supports_slot_serving(cfg):
            raise ValueError(
                f"{cfg.name}: slot serving needs input_mode='tokens' and no "
                "encoder stack (stub-embed / encoder-decoder frontends have "
                "no token prompts to prefill)")
        self.cfg, self.pcfg, self.mesh = cfg, pcfg, mesh
        # tensor parallelism: validated up front for a friendly error at
        # construction (the step builders re-check); the engine logic itself
        # is TP-transparent — params/caches stay GLOBAL arrays here, and the
        # TP step builders' shard_map splits heads/FFN columns (params) and
        # the KV-head dim (caches) on entry and rejoins on exit.
        self.tp_shards = int(getattr(pcfg, "tp_shards", 1) or 1)
        if self.tp_shards > 1:
            if "tp" not in mesh.axis_names \
                    or mesh.shape["tp"] != self.tp_shards:
                raise ValueError(
                    f"tp_shards={self.tp_shards} needs a 'tp' mesh axis of "
                    f"that size; mesh has {dict(mesh.shape)} (build one with "
                    "launch.mesh.make_tp_mesh)")
            tf.validate_tp(cfg, self.tp_shards)
        self.n_slots, self.max_len = n_slots, max_len
        self.cache_kinds = tf.cache_layer_kinds(cfg)
        self._has_attn = "attn" in self.cache_kinds
        # longest single prefill/verify CALL: every attention sublayer must
        # fit the chunk in its (possibly window/chunk-bounded) ring cache,
        # or one call would write a ring slot twice. Longer prompts are
        # CHUNKED across calls, not rejected. Pure-recurrent stacks have
        # no ring.
        s_min = tf.prefill_call_bound(cfg, max_len)
        self.max_prompt_len = s_min          # per-call bound (kept name: API)
        # the speculative in-call wrap hazard only exists where a ring is
        # narrower than the absolute-position capacity _check enforces
        self._bounded_ring = s_min < max_len
        # exact resume re-prefills prompt+journal under a new chunk plan.
        # Bounded rings are chunk-PLAN-deterministic only, and the
        # capacity-dispatch MoE drops whichever tokens overflow an expert
        # in a chunk: both restart from the prompt instead. The held-experts
        # layer drops nothing, so its results do not depend on the plan
        self._exact_resume = not self._bounded_ring and not (
            cfg.moe is not None and cfg.moe.impl == "dispatch")
        if draft_headroom is None:
            draft_headroom = SpecParams().draft_k
        self.draft_headroom = max(0, int(draft_headroom))
        self.prefill_chunk = (s_min if prefill_chunk is None
                              else min(prefill_chunk, s_min))
        if self.prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {prefill_chunk}")
        self.min_prefill_bucket = min(min_prefill_bucket, s_min)

        suite = ShapeSuite("serve", max_len, n_slots, "decode")
        self._suite = suite
        self._decode, sh = step_fns.make_serve_step(
            cfg, pcfg, mesh, suite, slots=True,
            ring_slack=self.draft_headroom)
        self._prefill, _ = step_fns.make_prefill_step(
            cfg, pcfg, mesh, suite, into_slots=True,
            ring_slack=self.draft_headroom)
        self._shardings = sh
        self.params = jax.device_put(params, step_fns._named(mesh,
                                                             sh["params"]))
        self._cache_sharding = step_fns._named(mesh, sh["cache"])
        self._reset = make_reset(self._cache_sharding)
        self.caches = None            # allocated per run
        self.stats_reducer = stats_reducer
        self.drafter = drafter
        # observability (repro.obs, docs/observability.md) — all optional,
        # all MUTABLE attrs read dynamically each tick, so a tracer or
        # metrics object can attach mid-run (e.g. after an untraced
        # baseline) and every hook stays one `is None` check when off.
        # ``tracer``        obs.Tracer event sink (pure observation);
        # ``metrics``       obs.StreamingMetrics — TTFT/latency histogram
        #                   increments appended to the per-tick stats row
        #                   (same b=1 reduction, wider payload);
        # ``metrics_every`` emit a live snapshot every N ticks (0 = off)
        #                   to ``metrics_sink(tick, snapshot)`` and/or the
        #                   tracer as a "metrics" event.
        self.tracer = tracer
        self.metrics = metrics
        self.metrics_every = int(metrics_every)
        self.metrics_sink = metrics_sink
        self._verify_steps: dict = {}   # draft budget K -> jitted verify
        # cross-request prefix caching: one jitted row snapshot (extract)
        # and one jitted copy-on-admit (adopt), slot traced so slot churn
        # never re-jits; adopt's output pinned to the cache sharding for
        # the same GSPMD reason as _reset. The trie itself is per-SESSION
        # (EngineSession builds it) — rows are pure functions of (params,
        # tokens), so scoping is a freshness choice, not a correctness one.
        self.prefix_enabled = bool(prefix_cache)
        self.prefix_cache_nodes = int(prefix_cache_nodes)
        if self.prefix_enabled:
            if self.prefix_cache_nodes < 1:
                raise ValueError(f"prefix_cache_nodes must be >= 1, got "
                                 f"{prefix_cache_nodes}")
            self._extract = jax.jit(extract_row)
            self._adopt = jax.jit(tf.adopt_prefix,
                                  out_shardings=self._cache_sharding)

    # ---------------------------------------------------------------- admin
    def _bucket(self, prompt_len: int) -> int:
        return min(_pow2_at_least(prompt_len, self.min_prefill_bucket),
                   self.max_prompt_len)

    def _check(self, req: Request) -> None:
        if self._has_attn and \
                len(req.prompt) + req.max_new_tokens > self.max_len:
            # ring capacity is absolute-position bound for full attention;
            # pure-recurrent stacks carry O(1) state and take any length
            raise ValueError(
                f"request {req.rid}: prompt+generation "
                f"{len(req.prompt) + req.max_new_tokens} exceeds cache "
                f"length {self.max_len}")
        if req.spec is not None:
            if not tf.supports_speculation(self.cfg):
                raise ValueError(
                    f"request {req.rid}: {self.cfg.name} has a cached "
                    "sublayer without a verify rollback rule "
                    "(supports_speculation)")
            if self._bounded_ring and req.spec.draft_k > self.draft_headroom:
                raise ValueError(
                    f"request {req.rid}: draft_k {req.spec.draft_k} exceeds "
                    f"the engine's draft_headroom {self.draft_headroom} — on "
                    "window/chunk-bounded rings a wider verify call would "
                    "overwrite live window positions")

    def _get_verify(self, draft_k: int):
        """The verify step compiled for draft budget K (cached per K; the
        adaptive controller varies k per request WITHIN K via n_draft)."""
        if draft_k not in self._verify_steps:
            step, _ = step_fns.make_verify_step(
                self.cfg, self.pcfg, self.mesh, self._suite, draft_k,
                ring_slack=self.draft_headroom)
            self._verify_steps[draft_k] = step
        return self._verify_steps[draft_k]

    def _chunk_plan(self, prompt, start: int = 0) -> list:
        """Split a prompt into prefill chunks — a pure function of the
        prompt length and engine constants (never of scheduling), so every
        policy chunks identically and token streams match bit-for-bit.
        ``start`` (always a multiple of ``prefill_chunk``: prefix-cache
        lookups return chunk-grid boundaries only) skips tokens already
        adopted from the prefix trie; the remaining chunks coincide with
        the cold plan's tail, so a warm admission feeds exactly
        ``ceil((len - start) / prefill_chunk)`` chunks."""
        c = self.prefill_chunk
        return [prompt[i:i + c] for i in range(start, len(prompt), c)]

    # ---------------------------------------------------------------- run
    def start(self, requests=(), *, static: bool = False,
              policy=None) -> "EngineSession":
        """Open an :class:`EngineSession` — the tick-stepping form of
        :meth:`run`. The session owns its caches, scheduler, and sampler
        state, so several sessions can share one engine's compiled steps
        (the fleet simulation runs one session per replica); more requests
        may be submitted while the session runs (failover re-admission).
        ``policy`` is a :class:`~repro.serving.slo.SchedulingPolicy`
        (None = the FIFO reference).
        """
        return EngineSession(self, requests, static=static, policy=policy)

    def run(self, requests, *, static: bool = False,
            max_ticks: int = 100_000, policy=None) -> dict:
        """Serve ``requests`` to completion; returns the telemetry report.

        ``static=True`` runs the batch-synchronous reference policy (admit
        only full batches into an all-free slot table) through the same
        jitted steps. Token streams are identical either way — each batch
        row's computation depends only on its own request, chunk plans and
        sampler keys only on the request itself — so the policies differ
        exactly in scheduling: slot occupancy, TTFT, and wall time. The
        same stream invariant holds for any ``policy``
        (:mod:`repro.serving.slo`): preemption journals and resumes
        exactly, so policies change WHEN tokens land, never WHAT.
        """
        session = self.start(requests, static=static, policy=policy)
        while session.running:
            if session.now >= max_ticks:
                raise RuntimeError(f"serving stalled after {max_ticks} ticks")
            session.tick()
        self.caches = session.caches
        return session.report()


class PoisonedLogits(RuntimeError):
    """Raised by a session when a decode/verify tick produced non-finite
    logits for one or more active slots (the in-graph guard's -1 sentinel).
    NO token from the poisoned tick was committed — every affected
    request's journal still ends at its last good token, so the fleet can
    quarantine the replica and fail its work over with exact resume."""

    def __init__(self, slots, rids):
        self.slots = tuple(slots)
        self.rids = tuple(rids)
        super().__init__(
            f"non-finite decode logits in slots {self.slots} "
            f"(requests {self.rids}); tick not committed")


class EngineSession:
    """One serving run in progress, advanced one :meth:`tick` at a time.

    :meth:`ServingEngine.run` is ``start`` + tick-to-completion; the fleet
    runner instead interleaves ticks of several sessions (one per replica)
    under a heartbeat monitor and a fault injector, which is what turns
    failover from an end-state assertion into a mid-run event.

    Exact resume: a request admitted with a non-empty committed-token
    journal (``req.tokens`` — preserved by ``requeue_front`` on failover)
    is re-prefilled over ``prompt + tokens[:-1]`` through the ordinary
    chunked-admission machinery (attention rings rebuild position-exact;
    SSM carries rebuild via the ``lengths=`` checkpoint paths), the
    prefill's re-derived token is DISCARDED (the journal is authoritative
    — for greedy requests it equals the last committed token, a tested
    invariant), and decode resumes feeding ``tokens[-1]`` at sampler
    cursor ``len(tokens)`` — the merged stream is bit-identical to an
    undisturbed run for greedy and sampled requests alike. On
    window/chunk-bounded rings (SWA), and for capacity-dispatch MoE models
    (which drop whichever tokens overflow an expert in a chunk), a resume
    falls back to the lossy restart-from-prompt: their results depend on
    the chunk PLAN, and a resume necessarily runs a different plan; the
    restart replays the ORIGINAL plan, so streams still come out identical.
    The held-experts MoE drops nothing and resumes exactly.

    Held-experts counts: on a model with held-experts MoE layers
    (``tf.moe_counted_layers``), every step also returns its per-layer
    counts (``tf.MOE_COUNTS``). They come to the host in the same transfer
    as the next fetched tokens; :attr:`moe_counts` lists those fetched in
    the last tick as ``(program, int array (layers, 3))``, program
    ``"prefill"``, ``"decode"`` or ``"verify"``, and the tick's ``stats``
    span carries them.

    Speculative requests are engine-global state (one drafter slot table);
    run several concurrent sessions only with ``spec=None`` requests.
    """

    def __init__(self, engine: ServingEngine, requests=(), *,
                 static: bool = False, policy=None):
        if static and policy is not None and policy.name != "fifo":
            raise ValueError(
                "static batching is the batch-synchronous FIFO reference; "
                f"it is not defined for policy {policy.name!r}")
        self.engine = engine
        self.static = static
        self.sched = SlotScheduler(engine.n_slots, policy=policy)
        self.k_run = 0
        self._ctrls: dict = {}
        self.caches = jax.device_put(
            tf.init_cache(engine.cfg, engine.n_slots, engine.max_len,
                          per_slot=True, ring_slack=engine.draft_headroom),
            engine._cache_sharding)
        self.last = np.zeros(engine.n_slots, np.int32)
        self.samp = sampling.slot_arrays(engine.n_slots)
        self.pending_chunks: dict = {}   # slot -> remaining prompt chunks
        self._resume_last: dict = {}     # slot -> journal tail to re-feed
        # cross-request prefix caching (docs/prefix_caching.md): the trie
        # is session state — rows snapshotted here were produced by this
        # session's caches, and per-session scoping keeps the fleet story
        # simple (each replica shares within itself). Pins hold in-flight
        # adoptions against LRU eviction; _prefix_hist remembers the full
        # normalized history per prefilling slot so boundary snapshots key
        # on tokens[0:p] even after req.tokens grows.
        self.prefix = (PrefixCache(grid=engine.prefill_chunk,
                                   max_nodes=engine.prefix_cache_nodes)
                       if engine.prefix_enabled else None)
        self._prefix_pins: dict = {}     # slot -> pinned trie key
        self._prefix_hist: dict = {}     # slot -> normalized history tuple
        self.log = TelemetryLog(engine.stats_reducer)
        self.now = 0
        self._t0 = time.perf_counter()
        # observability: which replica this session's trace events carry
        # (the fleet runner stamps its replica id here); per-tick TTFT /
        # latency observations feed the streaming histograms when
        # ``engine.metrics`` is attached.
        self.trace_replica = 0
        self._tick_ttfts: list = []
        self._tick_lats: list = []
        self.moe_counts: list = []       # fetched in the last tick
        self._moe_pending: list = []     # (program, device counts)
        if self.prefix is not None:
            self.prefix.on_event = self._prefix_event
        if self.prefix is not None and isinstance(engine.drafter,
                                                  NgramDrafter) \
                and engine.drafter.corpus is None:
            engine.drafter.corpus = self.prefix
        for req in requests:
            self.submit(req)

    def submit(self, req) -> None:
        """Queue one more request (initial workload or failover orphan)."""
        eng = self.engine
        with span(eng.tracer, "submit", self.now, rid=req.rid,
                  replica=self.trace_replica):
            eng._check(req)
            req.t_submit_ns = time.perf_counter_ns()
            if req.spec is not None:
                if eng.drafter is None:
                    eng.drafter = NgramDrafter()
                    if self.prefix is not None:
                        # trie doubles as the shared n-gram drafter corpus:
                        # cached sequences from OTHER requests seed proposals
                        # before a request's own history has any n-grams
                        eng.drafter.corpus = self.prefix
                if getattr(eng.drafter, "n_slots", eng.n_slots) != eng.n_slots:
                    raise ValueError(
                        "drafter slot table does not match the engine "
                        f"({eng.drafter.n_slots} != {eng.n_slots})")
                # one compiled verify width per session: the largest requested
                # draft budget (per-request k varies within it via n_draft),
                # bounded so a verify call never exceeds the per-call ring
                # limit (T <= S — same rule as prefill chunks)
                self.k_run = min(max(self.k_run, req.spec.draft_k),
                                 eng.max_prompt_len - 1)
            self.sched.submit(req)

    @property
    def running(self) -> bool:
        return self.sched.pending or bool(self.sched.active)

    def _release(self, slot: int, req, freed) -> None:
        """Free a finished request's slot (and its drafter/controller)."""
        self.sched.release(slot, self.now)
        freed[slot] = True
        if req.latency is not None:
            self._tick_lats.append(req.latency)
        tr = self.engine.tracer
        if tr is not None:
            tr.event("commit", self.now, rid=req.rid,
                     replica=self.trace_replica, slot=int(slot),
                     n_tokens=len(req.tokens), done=True,
                     latency_ticks=req.latency)
        if req.spec is not None:
            self.engine.drafter.release(slot)
            self._ctrls.pop(req.rid, None)

    def _prefix_event(self, name: str, **attrs) -> None:
        """Prefix-trie detail events (insert/evict/hit) forwarded to the
        tracer; one `is None` check when tracing is off."""
        tr = self.engine.tracer
        if tr is not None:
            tr.event(name, self.now, replica=self.trace_replica, **attrs)

    def _unpin(self, slot: int) -> None:
        """Drop a slot's prefix-trie pin (if any) and its history note —
        the adopted node becomes LRU-evictable again. Called when the
        slot's final chunk lands, on preemption, and on :meth:`abort`."""
        self._prefix_hist.pop(slot, None)
        key = self._prefix_pins.pop(slot, None)
        if key is not None and self.prefix is not None:
            self.prefix.release(key)

    def tick(self) -> list:
        """Run one engine iteration; returns (and logs) this tick's local
        stats vector (see ``telemetry.STATS_FIELDS``). Raises
        :class:`PoisonedLogits` — committing nothing from the tick — if
        the decode/verify guard flagged non-finite logits.

        Each phase of the tick runs inside a span
        (:func:`repro.obs.trace.span`): ``tick`` around them all, then
        ``schedule``, ``prefill_chunk``, ``decode``/``verify``, ``fetch``,
        ``commit``, ``reset`` and ``stats``. A running JAX profiler sees
        them as ``repro.serve.<name>``; an attached tracer records them."""
        # observability: read the mutable sinks ONCE per tick (late attach
        # is the supported idiom — see ServingEngine). Pure observation:
        # every hook records values the tick computed anyway and feeds
        # nothing back.
        tr = self.engine.tracer
        with span(tr, "tick", self.now, replica=self.trace_replica,
                  tick=self.now):
            return self._tick(tr)

    def _tick(self, tr) -> list:
        eng = self.engine
        sched = self.sched
        samp = self.samp
        now = self.now
        rep = self.trace_replica
        new_tokens = 0
        sampled_tokens = 0
        chunks_fed = 0
        prompt_tokens = 0
        drafted = 0
        accepted = 0
        resumed = 0
        deadline_misses = 0
        prefix_hits = 0
        prefix_reused = 0
        freed = np.zeros(eng.n_slots, bool)
        # the scheduler emits shed/preempt events at the decision site
        sched.tracer = tr
        sched.trace_replica = rep
        self._tick_ttfts = []
        self._tick_lats = []
        self.moe_counts = []

        with span(tr, "schedule", now, replica=rep):
            # --- SLO hooks: shed hopeless queued work, then evict slots
            # the policy wants for waiting higher-priority requests. Both
            # are no-ops under the FIFO reference policy. Eviction happens
            # BEFORE admission so a freed slot is re-granted in the same
            # tick, and the evicted rows are reset immediately (not at
            # end-of-tick with ``freed``) so the incoming request prefills
            # into a clean slot.
            shed_now = sched.shed(now)
            for req in shed_now:
                if req.deadline is not None and not req.deadline_counted:
                    req.deadline_counted = True
                    deadline_misses += 1
            preempt_slots = sched.plan_preemptions(now)
            if preempt_slots:
                mask = np.zeros(eng.n_slots, bool)
                for slot in preempt_slots:
                    req = sched.active[slot]
                    self.pending_chunks.pop(slot, None)
                    self._resume_last.pop(slot, None)
                    self._unpin(slot)
                    sampling.set_slot(samp, slot, None)
                    if req.spec is not None:
                        eng.drafter.release(slot)
                        self._ctrls.pop(req.rid, None)
                    sched.preempt(slot, now)
                    mask[slot] = True
                with span(tr, "reset", now, replica=rep,
                          n_slots=len(preempt_slots)):
                    self.caches = eng._reset(self.caches, jnp.asarray(mask))

            # --- admission: grant free slots, stage the chunk plans ----
            admissions = sched.admit(now, batch_sync=self.static)
            for slot, req in admissions:
                history = req.prompt
                if req.tokens and not eng._exact_resume:
                    # replay the original plan instead (lossy restart —
                    # same stream, more recompute)
                    req.tokens = []
                    req.t_first = None
                if req.tokens:
                    # exact resume: rebuild the cache over the journal;
                    # the last committed token is re-fed by decode, not
                    # re-derived
                    history = req.prompt + tuple(req.tokens[:-1])
                    self._resume_last[slot] = int(req.tokens[-1])
                    resumed += len(req.tokens)
                    req.resumed_tokens += len(req.tokens)
                    if tr is not None:
                        tr.event("resume", now, rid=req.rid, replica=rep,
                                 slot=int(slot),
                                 journal_tokens=len(req.tokens),
                                 preemptions=req.preemptions,
                                 failovers=req.failovers)
                start = 0
                if self.prefix is not None:
                    # prefix adoption AFTER history normalization: a
                    # resumed request matches against its journal-extended
                    # history, so a preempted request re-adopts its own
                    # boundaries. lookup() caps the match at
                    # len(history)-1 — at least one chunk always runs so
                    # the final chunk emits first-token logits through the
                    # ordinary prefill path.
                    p, node = self.prefix.lookup(history)
                    if node is not None:
                        self.caches = eng._adopt(
                            self.caches, node.row,
                            jnp.asarray(slot, jnp.int32))
                        self.prefix.acquire(node.key)
                        self._prefix_pins[slot] = node.key
                        start = p
                        req.prefilled = p  # resume=True from the 1st chunk
                        req.prefix_reused += p
                        prefix_hits += 1
                        prefix_reused += p
                        if tr is not None:
                            tr.event("prefix_adopt", now, rid=req.rid,
                                     replica=rep, slot=int(slot),
                                     tokens_reused=p)
                    self._prefix_hist[slot] = tuple(history)
                self.pending_chunks[slot] = eng._chunk_plan(history,
                                                            start=start)
                if tr is not None:
                    tr.event("admit", now, rid=req.rid, replica=rep,
                             slot=int(slot), prompt_len=len(req.prompt),
                             chunks=len(self.pending_chunks[slot]),
                             resumed=bool(req.tokens),
                             wait_ms=(None if req.t_submit_ns is None else
                                      (time.perf_counter_ns()
                                       - req.t_submit_ns) / 1e6))
                sampling.set_slot(samp, slot, req.sampling)
                if req.spec is not None:
                    self._ctrls[req.rid] = AdaptiveDraftController(req.spec)
                    eng.drafter.admit(slot, req)

        # --- prefill: one chunk per admitting slot per tick ------------
        # one single-row call per chunk (cost follows the admitted
        # prompt, not n_slots); the prompt bucket keeps Tc off the
        # compile-cache hot path. The final chunk emits the request's
        # first token (sampled; greedy rows bit-exact argmax) — except on
        # a resumed slot, whose next token is already in the journal.
        for slot in sorted(self.pending_chunks):
            req = sched.active[slot]
            chunk = self.pending_chunks[slot].pop(0)
            final = not self.pending_chunks[slot]
            tc = eng._bucket(len(chunk))
            with span(tr, "prefill_chunk", now, rid=req.rid, replica=rep,
                      slot=int(slot), bucket=tc, tokens=len(chunk),
                      final=final):
                buf = np.zeros((1, tc), np.int32)
                buf[0, :len(chunk)] = chunk
                sampled_req = (req.sampling is not None
                               and not req.sampling.greedy
                               and slot not in self._resume_last)
                tok, *n, self.caches = eng._prefill(
                    eng.params, jnp.asarray(buf), self.caches,
                    jnp.asarray(slot, jnp.int32),
                    jnp.asarray(len(chunk), jnp.int32),
                    resume=req.prefilled > 0,
                    sampling_row=({k: jnp.asarray(v[slot])
                                   for k, v in samp.items()}
                                  if sampled_req else None))
                self._moe_pending.extend(("prefill", c) for c in n)
            req.prefilled += len(chunk)
            chunks_fed += 1
            prompt_tokens += len(chunk)
            if self.prefix is not None:
                # snapshot the slot row at every chunk-grid boundary: the
                # row there is a pure function of history[:p] + the grid
                # (pads suppressed by ring validity / lengths= masking),
                # which is exactly what makes it adoptable by ANY later
                # request sharing those tokens. Valid on the final chunk
                # too — the post-prefill row precedes first-token
                # sampling, so it never depends on sampler state.
                p = req.prefilled
                hist = self._prefix_hist.get(slot, ())
                if p % eng.prefill_chunk == 0 and p <= len(hist):
                    key = hist[:p]
                    if key not in self.prefix:
                        self.prefix.insert(key, eng._extract(
                            self.caches, jnp.asarray(slot, jnp.int32)))
            if final:
                del self.pending_chunks[slot]
                self._unpin(slot)
                req.state = RequestState.ACTIVE
                if slot in self._resume_last:
                    # journal is authoritative: discard the re-derived
                    # token, resume decode from the committed tail
                    self.last[slot] = self._resume_last.pop(slot)
                    continue
                with span(tr, "fetch", now, replica=rep, of="prefill"):
                    tok = int(self._fetch(tok))
                with span(tr, "commit", now, replica=rep):
                    req.tokens.append(tok)
                    req.t_first = now
                    self._tick_ttfts.append(req.ttft)
                    if tr is not None:
                        tr.event("commit", now, rid=req.rid, replica=rep,
                                 slot=int(slot), n_tokens=1,
                                 first_token=True, ttft_ticks=req.ttft)
                    if req.deadline is not None \
                            and not req.deadline_counted \
                            and now > req.deadline:
                        req.deadline_counted = True
                        deadline_misses += 1
                    self.last[slot] = tok
                    new_tokens += 1
                    if req.sampling is not None and not req.sampling.greedy:
                        sampled_tokens += 1
                    if req.done:
                        self._release(slot, req, freed)

        # --- draft: propose up to k tokens per speculative slot --------
        decodable = {slot: req for slot, req in sched.active.items()
                     if req.state is RequestState.ACTIVE}
        drafts: dict = {}
        for slot, req in decodable.items():
            if req.spec is None:
                continue
            # never draft past the request's budget: the verify call
            # emits at most k+1 tokens, and capping k at remaining-1
            # also keeps every REAL written position inside the ring
            # bound _check admitted against (pad columns never write —
            # lengths= suppression inside the verify step)
            k_eff = min(self._ctrls[req.rid].current_k(), self.k_run,
                        req.max_new_tokens - len(req.tokens) - 1)
            if k_eff > 0:
                d = eng.drafter.propose(slot, req, k_eff)[:k_eff]
                if d:
                    drafts[slot] = [int(t) for t in d]
                if tr is not None:
                    tr.event("draft", now, rid=req.rid, replica=rep,
                             slot=int(slot), k_eff=int(k_eff),
                             proposed=len(d),
                             drafter=drafter_label(eng.drafter))

        if decodable:
            step = "verify" if drafts else "decode"
            with span(tr, step, now, replica=rep, n_active=len(decodable)):
                active = np.zeros(eng.n_slots, bool)
                steps = np.zeros(eng.n_slots, np.int32)
                any_sampled = False
                for slot, req in decodable.items():
                    active[slot] = True
                    steps[slot] = len(req.tokens)
                    any_sampled |= (req.sampling is not None
                                    and not req.sampling.greedy)
                # all-greedy ticks take the argmax-only jitted variant;
                # the sampled variant's greedy rows are the same argmax,
                # so mixing never changes a greedy request's stream
                samp_in = ({"key": jnp.asarray(samp["key"]),
                            "step": jnp.asarray(steps),
                            "temperature": jnp.asarray(samp["temperature"]),
                            "top_k": jnp.asarray(samp["top_k"]),
                            "top_p": jnp.asarray(samp["top_p"])}
                           if any_sampled else None)
                if drafts:
                    # --- verify: score k+1 positions per slot in one
                    # pass, emit the longest committed-stream-matching
                    # prefix
                    buf = np.zeros((eng.n_slots, self.k_run + 1), np.int32)
                    buf[:, 0] = self.last
                    n_draft = np.zeros(eng.n_slots, np.int32)
                    for slot, d in drafts.items():
                        buf[slot, 1:1 + len(d)] = d
                        n_draft[slot] = len(d)
                    out, acc, *n, self.caches = eng._get_verify(
                        self.k_run)(
                        eng.params, jnp.asarray(buf), self.caches,
                        jnp.asarray(active), jnp.asarray(n_draft), samp_in)
                else:
                    # --- decode: one token per busy slot (no proposals)
                    toks, *n, self.caches = eng._decode(
                        eng.params,
                        {"tokens": jnp.asarray(self.last[:, None])},
                        self.caches, jnp.asarray(active), samp_in)
                self._moe_pending.extend((step, c) for c in n)
            if drafts:
                with span(tr, "fetch", now, replica=rep, of="verify"):
                    out, acc = self._fetch((out, acc))
                    out, acc = out.astype(np.int32), acc.astype(np.int32)
                with span(tr, "commit", now, replica=rep):
                    self._guard(decodable, [out[s, :acc[s]].min(initial=0)
                                            for s in decodable])
                    for slot, req in decodable.items():
                        n = int(acc[slot])
                        emit = [int(t) for t in out[slot, :n]]
                        req.tokens.extend(emit)
                        self.last[slot] = emit[-1]
                        new_tokens += len(emit)
                        if req.sampling is not None \
                                and not req.sampling.greedy:
                            sampled_tokens += len(emit)
                        nd = int(n_draft[slot])
                        drafted += nd
                        accepted += n - 1
                        if req.spec is not None:
                            self._ctrls[req.rid].update(nd, n - 1)
                        if tr is not None:
                            tr.event("commit", now, rid=req.rid,
                                     replica=rep, slot=int(slot),
                                     n_tokens=len(emit), n_draft=nd,
                                     accepted=n - 1)
                        if req.done:
                            self._release(slot, req, freed)
            else:
                with span(tr, "fetch", now, replica=rep, of="decode"):
                    toks = self._fetch(toks).astype(np.int32)
                with span(tr, "commit", now, replica=rep):
                    self._guard(decodable, [toks[s] for s in decodable])
                    for slot, req in decodable.items():
                        req.tokens.append(int(toks[slot]))
                        self.last[slot] = toks[slot]
                        new_tokens += 1
                        if req.sampling is not None \
                                and not req.sampling.greedy:
                            sampled_tokens += 1
                        if tr is not None:
                            tr.event("commit", now, rid=req.rid,
                                     replica=rep, slot=int(slot),
                                     n_tokens=1)
                        if req.done:
                            self._release(slot, req, freed)

        if freed.any():
            with span(tr, "reset", now, replica=rep,
                      n_slots=int(freed.sum())):
                self.caches = eng._reset(self.caches, jnp.asarray(freed))
                for slot in np.flatnonzero(freed):
                    sampling.set_slot(samp, int(slot), None)
        queue_depth = sched.arrived_depth(now)
        with span(tr, "stats", now, replica=rep, queue_depth=queue_depth,
                  active_slots=len(sched.active), prefills=len(admissions),
                  prompt_tokens=prompt_tokens, new_tokens=new_tokens,
                  **self._moe_attrs()):
            # build the stats row BY NAME through the drift guard: a
            # counter added here but not to STATS_FIELDS (or vice versa)
            # fails on the first tick instead of silently skewing the b=1
            # fleet reduction
            vec = stats_vector({
                "queue_depth": queue_depth,
                "active_slots": len(sched.active),
                "new_tokens": new_tokens,
                "prefills": len(admissions),
                "prefill_chunks": chunks_fed,
                "sampled_tokens": sampled_tokens,
                "drafted_tokens": drafted,
                "accepted_tokens": accepted,
                "failovers": 0,       # control-plane: counted by the fleet
                "resumed_tokens": resumed,
                "quarantines": 0,     # control-plane: counted by the fleet
                "preemptions": len(preempt_slots),
                "shed_requests": len(shed_now),
                "deadline_misses": deadline_misses,
                "prefix_hits": prefix_hits,
                "prefix_tokens_reused": prefix_reused,
            })
            metrics = eng.metrics
            if metrics is not None:
                # histogram increments ride the SAME b=1 stats reduction —
                # the row just gets a fixed-width tail (the reducer is
                # width-agnostic); counts land in the histograms only via
                # the reduced vector, so single-engine and fleet runs
                # agree.
                vec = vec + metrics.row(self._tick_ttfts, self._tick_lats)
            self.log.step(now, vec)
            if metrics is not None:
                metrics.absorb(self.log.last_reduced[len(STATS_FIELDS):])
                every = eng.metrics_every
                if every > 0 and (now + 1) % every == 0:
                    snap = metrics.snapshot()
                    if eng.metrics_sink is not None:
                        eng.metrics_sink(now, snap)
                    if tr is not None:
                        tr.event("metrics", now, replica=rep, **snap)
        self.now += 1
        return vec

    def _fetch(self, value):
        """Copy a step's result to the host, and in the same transfer the
        held-experts counts of every step since the last fetch."""
        pending, self._moe_pending = self._moe_pending, []
        value, counts = jax.device_get((value, [c for _, c in pending]))
        self.moe_counts.extend((prog, np.asarray(c))
                               for (prog, _), c in zip(pending, counts))
        return value

    def _moe_attrs(self) -> dict:
        """The ``stats`` span's held-experts attrs: the programs whose
        counts this tick fetched (``moe_steps``), and per count a string
        of each step's per-layer values, steps apart by ``;``."""
        if not self.moe_counts:
            return {}
        out = {"moe_steps": ",".join(p for p, _ in self.moe_counts)}
        for i, name in enumerate(tf.MOE_COUNTS):
            out[name] = ";".join(",".join(str(int(v)) for v in c[:, i])
                                 for _, c in self.moe_counts)
        return out

    def _guard(self, decodable, slot_tokens) -> None:
        """Refuse a tick whose guard flagged non-finite logits: raise with
        the poisoned slots BEFORE any of the tick's tokens commit."""
        bad = [slot for slot, tok in zip(decodable, slot_tokens)
               if int(tok) < 0]
        if bad:
            raise PoisonedLogits(bad, [decodable[s].rid for s in bad])

    def abort(self) -> list:
        """Evict every in-flight request (replica death in the fleet sim);
        returns them — journals intact — for re-queueing elsewhere."""
        self.pending_chunks.clear()
        self._resume_last.clear()
        for slot in list(self._prefix_pins):
            self._unpin(slot)
        self._prefix_hist.clear()
        return self.sched.drain_active()

    def report(self) -> dict:
        wall = time.perf_counter() - self._t0
        log, sched = self.log, self.sched
        report = log.report(sched.finished, wall, self.now)
        report["mode"] = "static" if self.static else "continuous"
        report["tokens"] = {r.rid: list(r.tokens) for r in sched.finished}
        for field in ("sampled_tokens", "prefill_chunks", "drafted_tokens",
                      "accepted_tokens", "resumed_tokens", "failovers",
                      "quarantines", "preemptions", "shed_requests",
                      "deadline_misses", "prefix_hits",
                      "prefix_tokens_reused"):
            report[field] = int(sum(getattr(s, field) for s in log.steps))
        if self.prefix is not None:
            report["prefix_cache"] = self.prefix.stats()
        report["acceptance_rate"] = (
            report["accepted_tokens"] / report["drafted_tokens"]
            if report["drafted_tokens"] else float("nan"))
        report["policy"] = sched.policy.name
        report["tp"] = self.engine.tp_shards
        report["slo"] = slo_report(sched.finished + sched.shed_requests)
        if self.engine.metrics is not None:
            report["live_metrics"] = self.engine.metrics.snapshot()
        return report
