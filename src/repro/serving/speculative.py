"""Speculative decoding over the slot machinery: drafters + controllers.

Why here, in *this* repo: every serving tick costs at least one
latency-bound b=1 dual-root stats reduction — the small-message
``O(alpha * log p)`` regime the source paper's latency term describes.
Speculative decoding amortizes that fixed per-tick cost: a cheap DRAFTER
proposes up to k next tokens per request, one jitted VERIFY pass scores all
k+1 positions against the per-slot caches
(:func:`repro.launch.step_fns.make_verify_step`), and the engine emits the
longest draft prefix the model itself agrees with plus the model's own
token at the first disagreement. Every tick still pays one reduction, but
now emits up to k+1 tokens — fewer reduction ticks per emitted token, with
streams BIT-IDENTICAL to the non-speculative engine (greedy rows accept
against the exact argmax; sampled rows against the committed
``fold_in(seed, token_index)`` sampler, see
:mod:`repro.serving.sampling`), so speculation is a pure scheduling win,
like continuous batching before it.

Two drafters behind one duck-typed protocol (``admit(slot, req)`` /
``propose(slot, req, k) -> list[int]`` / ``release(slot)``):

* :class:`NgramDrafter` — prompt-lookup self-drafting: propose the tokens
  that followed the most recent earlier occurrence of the request's own
  trailing n-gram. No second model, no device state; a pure function of
  the request's (prompt + emitted) history, so proposals are
  schedule-independent — tick counts reproduce run-to-run.
* :class:`DraftModelDrafter` — a second (smaller) parameter set running
  through its OWN per-slot caches and jitted slot steps. Its caches only
  ever hold committed tokens: proposing snapshots the cache pytree,
  decodes k greedy draft steps, then restores the snapshot (the jitted
  steps are built with ``donate=False`` for exactly this), and accepted
  tokens are re-fed as catch-up on the next proposal.

:class:`AdaptiveDraftController` shrinks the per-request draft length when
the acceptance-rate EWMA drops (wide drafts on a disagreeing model waste
verify width) and grows it back when acceptance recovers — always within
the compiled budget ``SpecParams.draft_k``, so adaptation never re-jits.
Per-tick ``drafted_tokens`` / ``accepted_tokens`` counters ride the same
b=1 dual-root stats reduction (:mod:`repro.serving.telemetry`).

Full invariants and the rollback story: docs/speculative.md.
"""

from __future__ import annotations

import dataclasses

import numpy as np

# Hard ceiling on the per-request draft budget: the verify pass scores
# draft_k + 1 positions per tick, and a verify call must stay well under
# any ring-cache length (T <= S per call).
MAX_DRAFT_K = 16


@dataclasses.dataclass(frozen=True)
class SpecParams:
    """Per-request speculative-decoding controls.

    draft_k: maximum drafts per tick (the verify step's compiled width).
    min_k: adaptation floor — the controller never proposes fewer.
    ngram: longest trailing n-gram the lookup drafter tries to match.
    adapt: enable the acceptance-EWMA draft-length controller.
    low/high: acceptance-rate thresholds — below ``low`` the controller
        shrinks k by one, above ``high`` it grows k by one (within
        [min_k, draft_k]).
    ewma: smoothing weight of the newest tick's acceptance rate.
    """

    draft_k: int = 4
    min_k: int = 1
    ngram: int = 3
    adapt: bool = True
    low: float = 0.3
    high: float = 0.7
    ewma: float = 0.4

    def __post_init__(self):
        if not 1 <= self.draft_k <= MAX_DRAFT_K:
            raise ValueError(
                f"draft_k must be in [1, {MAX_DRAFT_K}], got {self.draft_k}")
        if not 1 <= self.min_k <= self.draft_k:
            raise ValueError(
                f"min_k must be in [1, draft_k={self.draft_k}], "
                f"got {self.min_k}")
        if self.ngram < 1:
            raise ValueError(f"ngram must be >= 1, got {self.ngram}")
        if not 0.0 <= self.low <= self.high <= 1.0:
            raise ValueError(
                f"need 0 <= low <= high <= 1, got {self.low}/{self.high}")
        if not 0.0 < self.ewma <= 1.0:
            raise ValueError(f"ewma must be in (0, 1], got {self.ewma}")


class AdaptiveDraftController:
    """Per-request draft-length adaptation on an acceptance-rate EWMA.

    Deterministic: the state is a pure function of the request's own
    (drafted, accepted) history, never of scheduling — so like the chunk
    plans and sampler keys, adaptation cannot make two runs of the same
    workload diverge. Starts optimistic (full ``draft_k``): the first
    disagreeing ticks pay at most ``draft_k`` wasted verify positions
    before the EWMA pulls k down.
    """

    def __init__(self, spec: SpecParams):
        self.spec = spec
        self.k = spec.draft_k
        self.rate = 1.0
        self.drafted = 0
        self.accepted = 0

    def current_k(self) -> int:
        return self.k

    def snapshot(self) -> dict:
        """JSON-safe controller state (trace/metrics attrs): current k,
        the acceptance EWMA, and lifetime drafted/accepted totals."""
        return {"k": int(self.k), "rate": float(self.rate),
                "drafted": int(self.drafted), "accepted": int(self.accepted)}

    def update(self, n_draft: int, n_accept: int) -> int:
        """Record one verify tick's outcome; returns the next tick's k."""
        self.drafted += int(n_draft)
        self.accepted += int(n_accept)
        if not self.spec.adapt or n_draft == 0:
            return self.k
        a = self.spec.ewma
        self.rate = (1.0 - a) * self.rate + a * (n_accept / n_draft)
        if self.rate < self.spec.low:
            self.k = max(self.spec.min_k, self.k - 1)
        elif self.rate > self.spec.high:
            self.k = min(self.spec.draft_k, self.k + 1)
        return self.k


def drafter_label(drafter) -> str:
    """Short stable label for trace events: which drafting strategy a
    draft proposal came from (``"ngram"``, ``"draft_model"``, ``"none"``,
    or the class name for custom drafters)."""
    if drafter is None:
        return "none"
    if isinstance(drafter, NgramDrafter):
        return "ngram"
    if isinstance(drafter, DraftModelDrafter):
        return "draft_model"
    return type(drafter).__name__


class Drafter:
    """Drafter protocol (base no-op implementation).

    ``admit`` is called when a speculative request is granted a slot,
    ``release`` when it completes or fails over; ``propose`` may return
    FEWER than ``k`` tokens (or none — the tick then degenerates to a plain
    decode step for that slot). Proposals must depend only on the request's
    own history, never on scheduling, or run-to-run tick determinism is
    lost.
    """

    def admit(self, slot: int, req) -> None:
        pass

    def release(self, slot: int) -> None:
        pass

    def propose(self, slot: int, req, k: int) -> list:
        return []


class NgramDrafter(Drafter):
    """Prompt-lookup self-drafting (no extra model).

    Find the most recent earlier occurrence of the request's trailing
    n-gram (longest first, down to a single token) in its own
    prompt + generated history, and propose the tokens that followed it.
    Free to run on the CPU simulator, surprisingly effective on repetitive
    text, and exactly the prompt-lookup decoding trick used as the
    model-free baseline in assisted-generation stacks.

    ``corpus`` (optional — the engine wires the session's
    :class:`~repro.serving.prefix.PrefixCache` in when prefix caching is
    on) is a shared fallback searched AFTER the request's own history
    misses: anything with a ``sequences() -> list[tuple]`` view of cached
    token runs. Shared system prompts and few-shot prefixes are exactly
    the text many requests repeat, so the trie is strong draft material a
    single request's history cannot see. Corpus proposals depend on what
    OTHER requests have prefilled, so they may change how many ticks a
    stream takes between runs with different trie contents — never the
    stream itself (the verify step accepts only what the committed
    greedy/sampled stream would emit). Own-history proposals keep their
    precedence, so with an empty or absent corpus behavior is unchanged.
    """

    def __init__(self, max_ngram: int = 3, corpus=None):
        if max_ngram < 1:
            raise ValueError(f"max_ngram must be >= 1, got {max_ngram}")
        self.max_ngram = max_ngram
        self.corpus = corpus

    def propose(self, slot: int, req, k: int) -> list:
        hist = tuple(req.prompt) + tuple(req.tokens)
        # the request's own SpecParams.ngram takes precedence; the
        # drafter-level max_ngram is only the fallback default
        spec_n = getattr(getattr(req, "spec", None), "ngram", None)
        n_cap = spec_n if spec_n else self.max_ngram
        for n in range(min(n_cap, len(hist) - 1), 0, -1):
            suffix = hist[-n:]
            # most recent occurrence strictly before the trailing one
            for start in range(len(hist) - n - 1, -1, -1):
                if hist[start:start + n] == suffix:
                    follow = hist[start + n:start + n + k]
                    if follow:
                        return [int(t) for t in follow]
                    break               # suffix only recurs at the very end
        if self.corpus is not None:
            return self._propose_from_corpus(hist, n_cap, k)
        return []

    def _propose_from_corpus(self, hist: tuple, n_cap: int, k: int) -> list:
        """Shared-corpus fallback: longest trailing n-gram first, scanning
        the corpus sequences in their (deterministic) insertion order and
        taking the most recent in-sequence occurrence."""
        seqs = self.corpus.sequences()
        for n in range(min(n_cap, len(hist)), 0, -1):
            suffix = hist[-n:]
            for seq in seqs:
                for start in range(len(seq) - n, -1, -1):
                    if seq[start:start + n] == suffix:
                        follow = seq[start + n:start + n + k]
                        if follow:
                            return [int(t) for t in follow]
                        break       # match only at the sequence's very end
        return []


class DraftModelDrafter(Drafter):
    """Draft-model drafting: a second parameter set on its own slot caches.

    The draft model mirrors the engine's slot layout (same ``n_slots``, its
    own ``max_len``) and runs the same jitted slot prefill/decode steps —
    built with ``donate=False`` so the pre-proposal cache snapshot stays
    valid. Invariant: between proposals the draft caches hold ONLY
    committed (prompt + emitted) tokens. ``propose`` first catches the slot
    up on tokens emitted since the last call, snapshots the cache pytree
    (immutable arrays — holding the old references is free), greedily
    decodes up to ``k`` draft steps, then restores the snapshot: rejected
    drafts leave no trace, and accepted ones are re-fed as the next
    catch-up.
    """

    def __init__(self, cfg, params, mesh, pcfg=None, *, n_slots: int,
                 max_len: int = 128, min_prefill_bucket: int = 8):
        import jax

        from repro.configs.base import ParallelConfig, ShapeSuite
        from repro.launch import step_fns
        from repro.models import transformer as tf

        if not tf.supports_slot_serving(cfg):
            raise ValueError(f"{cfg.name}: draft model must support slot "
                             "serving (token prompts, decoder-only)")
        self.cfg, self.mesh, self.n_slots = cfg, mesh, n_slots
        self.max_len = max_len
        pcfg = pcfg or ParallelConfig()
        self._bound = tf.prefill_call_bound(cfg, max_len)
        self._min_bucket = min(min_prefill_bucket, self._bound)
        suite = ShapeSuite("draft", max_len, n_slots, "decode")
        self._decode, sh = step_fns.make_serve_step(cfg, pcfg, mesh, suite,
                                                    slots=True, donate=False)
        self._prefill, _ = step_fns.make_prefill_step(
            cfg, pcfg, mesh, suite, into_slots=True, donate=False)
        self._cache_sharding = step_fns._named(mesh, sh["cache"])
        self.params = jax.device_put(params,
                                     step_fns._named(mesh, sh["params"]))
        self._reset = jax.jit(tf.reset_cache_slots,
                              out_shardings=self._cache_sharding)
        self.caches = None
        self._fed: dict = {}            # slot -> committed tokens in cache

    # ------------------------------------------------------------ plumbing
    def _ensure_caches(self):
        if self.caches is None:
            import jax

            from repro.models import transformer as tf
            self.caches = jax.device_put(
                tf.init_cache(self.cfg, self.n_slots, self.max_len,
                              per_slot=True), self._cache_sharding)

    def _feed(self, slot: int, tok: int) -> int:
        """Advance one slot by one token; returns the draft model's greedy
        next-token choice."""
        import jax.numpy as jnp
        active = np.zeros(self.n_slots, bool)
        active[slot] = True
        toks = np.zeros((self.n_slots, 1), np.int32)
        toks[slot, 0] = tok
        out, *_, self.caches = self._decode(self.params,
                                            {"tokens": jnp.asarray(toks)},
                                            self.caches, jnp.asarray(active))
        return int(np.asarray(out)[slot])

    # ------------------------------------------------------------ protocol
    def admit(self, slot: int, req) -> None:
        """Prefill the request's prompt into the draft slot (chunked by the
        draft model's own cache geometry; the emitted first token is
        discarded — the TARGET model's stream is the only stream)."""
        import jax.numpy as jnp

        # lazy: engine imports this module at import time (no cycle here)
        from repro.serving.engine import _pow2_at_least
        self._ensure_caches()
        free = np.zeros(self.n_slots, bool)
        free[slot] = True
        self.caches = self._reset(self.caches, jnp.asarray(free))
        prompt = tuple(req.prompt)
        pos = 0
        while pos < len(prompt):
            chunk = prompt[pos:pos + self._bound]
            tc = min(_pow2_at_least(len(chunk), self._min_bucket),
                     self._bound)
            buf = np.zeros((1, tc), np.int32)
            buf[0, :len(chunk)] = chunk
            _, *_, self.caches = self._prefill(
                self.params, jnp.asarray(buf), self.caches,
                jnp.asarray(slot, jnp.int32),
                jnp.asarray(len(chunk), jnp.int32), resume=pos > 0)
            pos += len(chunk)
        self._fed[slot] = len(prompt)

    def release(self, slot: int) -> None:
        import jax.numpy as jnp
        self._fed.pop(slot, None)
        if self.caches is not None:
            free = np.zeros(self.n_slots, bool)
            free[slot] = True
            self.caches = self._reset(self.caches, jnp.asarray(free))

    def propose(self, slot: int, req, k: int) -> list:
        stream = tuple(req.prompt) + tuple(req.tokens)
        committed = len(stream) - 1     # the final token is fed speculatively
        for tok in stream[self._fed.get(slot, 0):committed]:
            self._feed(slot, tok)       # catch up on accepted tokens
        self._fed[slot] = committed
        saved = self.caches             # snapshot: donate=False keeps it live
        last = int(stream[-1])
        drafts = []
        for _ in range(k):
            last = self._feed(slot, last)
            drafts.append(last)
        self.caches = saved             # drafts are speculative: roll back
        return drafts
