"""Serving engine: continuous batching with scheduler-driven slot admission.

Two serve modes share one decode specialization:

``continuous`` (default) — the ParallelFor reading of serving, end to end:
pending requests are the iteration space, ``cfg.slots`` decode slots are
the threads, and the admission policy (any registered scheduler —
``faa`` models one contended admission counter, ``hierarchical``
per-group admission lanes, ``stealing`` per-slot local queues) claims
requests via :class:`repro.serve.queue.RequestQueue`.  Decode never
stops for a refill: every step runs the full fixed-shape batch, and a
finished slot is refilled *in flight* — the incoming prompt is prefilled
at a bucketed width (pad-masked, so mixed lengths batch safely and one
jit specialization covers a whole bucket), its cache row spliced into
the freed slot, and the batch shape never changes, so there is exactly
one decode specialization total.  Per-request latency/throughput
telemetry accumulates in ``self.last_report``
(:class:`repro.serve.telemetry.ServeReport`).

``rounds`` — the legacy round-barrier fallback: cohorts of up to
``slots`` requests generate() together and the batch drains fully before
the next cohort starts.  Its historical head-of-line hazard (cohorts
restricted to same-length prompts, so a short cohort left slots empty
even with requests pending) is fixed: pad-masked prefill lets any
``slots`` consecutive pending requests batch regardless of width.

Decode runs the model's cache path (absorbed-MLA / SSD state / KV cache
per family); greedy or temperature sampling.  Under greedy decoding both
modes are bit-identical to per-request ``generate()`` calls for the
dense/ssm/hybrid families unconditionally; for ``moe`` the equivalence
additionally needs the batched router to stay within expert capacity,
which the capacity floor guarantees whenever ``slots * top_k <= 8``
(beyond that, a hot expert can drop choices in the batch that a
batch-of-1 would keep).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import faults as _faults
from repro.core import parallel_for as pf
from repro.core import runtime as rt
from repro.models.model import Model
from repro.serve.queue import Request, RequestQueue, as_requests
from repro.serve.telemetry import RequestTelemetry, ServeReport

# token-only families the serve path accepts (vlm/encdec need modal inputs
# that a 1-D token prompt cannot carry)
_SERVABLE = ("dense", "moe", "ssm", "hybrid")

# host spans in the profiler's own trace, on the device trace's clock
# (about a microsecond each while no profiler runs); docs/serving.md
# lists the span tree
_span = jax.profiler.TraceAnnotation


@dataclasses.dataclass
class SpecConfig:
    """Draft-model speculation for the continuous decode loop.

    A cheap ``draft`` model proposes ``k`` tokens per live slot per tick
    (sequential drafter decode steps, batched across slots); the target
    verifies all k+1 positions in ONE batched forward
    (:meth:`repro.models.model.Model.verify_step`), and greedy acceptance
    is longest-matching-prefix + one corrected token — so speculative
    serve output is bit-identical to target-only greedy serve, while one
    verification amortizes the per-token claim/admission bookkeeping over
    the whole accepted span (the paper's grain trade at serving
    granularity).  ``k=None`` resolves from the calibrated
    ``TuningContext.draft_span`` — mirroring ``admission_block``.
    Both target and drafter must support rollback-by-length-truncation
    (``Model.supports_speculation``: dense, non-MLA) and share a vocab;
    speculation is greedy-only (temperature must be 0).
    """

    draft: Model
    draft_params: object
    k: Optional[int] = None


@dataclasses.dataclass
class ServeConfig:
    max_len: int = 512
    eos_id: int = -1            # -1 = never stops early
    temperature: float = 0.0    # 0 = greedy
    cache_dtype: str = "float32"
    # KV cache storage dtype; None = cache_dtype.  "int8" (or
    # "float8_e4m3fn" where jax has it) stores quantized values plus
    # per-token f16 scales — roughly half the cache bytes, so a fixed
    # page-pool budget admits ~2x the concurrent slots (see
    # repro.kernels.quant.kv_byte_ratio and benchmarks/serve_paged_sweep).
    kv_dtype: Optional[str] = None
    slots: int = 4              # fixed batch slots for serve()
    refill_schedule: str = "static"  # admission / refill-packing policy
    refill_threads: int = 4     # rounds mode: host threads for the packing
    mode: str = "continuous"    # "continuous" | "rounds" (legacy barrier)
    # requests claimed per admission FAA; None = ask the calibrated
    # TuningContext (repro.core.runtime.tuning().admission_block — block 1
    # for small queues, amortized batches once the queue is deep)
    admission_block: Optional[int] = None
    # prefill widths to specialize (pad-safe families only); None = powers
    # of two from 8.  Exact lengths are used where padding is unsafe.
    prefill_buckets: Optional[Sequence[int]] = None
    # ---- cache backend (continuous mode) ----
    cache: str = "contiguous"   # "contiguous" | "paged"
    # tokens per KV page (must divide max_len); None = resolve the tuned
    # page size from the autotuner db (paged_decode_attention bucket with
    # the page_size-sweep sentinel) for this max_len / head_dim / kv dtype
    page_size: Optional[int] = 16
    # pool pages; None = slots * max_len / page_size (same KV bytes as the
    # contiguous engine — shrink it to trade memory against deferrals)
    num_pages: Optional[int] = None
    prefix_cache: bool = True   # shared-prefix page reuse (paged + dense)
    # free-list claim policy; None = refill_schedule (one knob drives both
    # the admission counter and the page counter)
    page_alloc_schedule: Optional[str] = None
    page_alloc_block: Optional[int] = None  # pages per claim FAA
    # aging bound on admission deferral: once a request has been pushed
    # back this many times under page pressure, other free slots stop
    # admitting (they re-queue without penalty) until it gets in — running
    # slots drain, pages free, and the large request stops losing every
    # race to smaller ones behind it.  None disables the barrier.
    max_deferred_ticks: Optional[int] = 32
    # ---- graceful degradation (see docs/robustness.md) ----
    # decode-tick deadline per admission: a request that has decoded this
    # many ticks without finishing is cancelled mid-decode (slot freed,
    # partial tokens discarded) and retried or failed.  None = no deadline.
    deadline_ticks: Optional[int] = None
    # cancelled / poisoned admissions re-enter the queue this many times
    # before the request goes terminal FAILED
    max_retries: int = 0
    # retry k re-enters admission after backoff * 2**(k-1) ticks; the
    # queue ages the delay without holding a slot
    backoff: float = 1.0
    # what an admission deadlock (nothing live, nothing admittable) does:
    #   "raise" — RuntimeError, destroying every in-flight result (the
    #             pre-robustness behavior; kept the default)
    #   "shed"  — drop the youngest deferred pending request with a SHED
    #             terminal status and keep admitting the rest
    #   "defer" — never raise: requests that can never admit go terminal
    #             FAILED and the batch completes around them
    on_pressure: str = "raise"
    # per-request failure isolation: an exception confined to one
    # request's admission or decode boundary marks that request FAILED
    # (its pages/slots reclaimed) instead of destroying the batch.
    # False restores propagate-everything.
    isolate_failures: bool = True
    # ---- speculative decoding (continuous mode, greedy only) ----
    # None = non-speculative decode; see SpecConfig
    spec: Optional[SpecConfig] = None


class Engine:
    def __init__(self, model: Model, params, cfg: ServeConfig):
        self.model = model
        self.params = params
        self.cfg = cfg
        # storage dtype of every KV cache this engine allocates (prefill
        # caches, contiguous rows, page pools); quantized dtypes make the
        # model's caches carry scale leaves — see models/attention.py
        self.kv_dtype = jnp.dtype(cfg.kv_dtype or cfg.cache_dtype)
        kvd = self.kv_dtype

        # every program is a named def, so a profiler trace names it
        # ``jit_<def>``; only the target's own step is ``jit_decode_step``
        def prefill(p, b):
            return model.prefill(p, b, cfg.max_len, kvd)

        def prefill_padded(p, toks, lens):
            return model.prefill_padded(
                p, {"tokens": toks, "lengths": lens}, cfg.max_len, kvd)

        # greedy decode transfers [B] token ids, never [B, vocab] logits
        def argmax_tokens(logits):
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)

        self._prefill = jax.jit(prefill)
        self._prefill_padded = jax.jit(prefill_padded)
        # a cache the model writes in place is donated (see _decode)
        self._decode_in_place = jax.jit(model.decode_step, donate_argnums=2)
        self._decode_kept = jax.jit(model.decode_step)
        self._argmax = jax.jit(argmax_tokens)
        # temperature > 0: one batched categorical per tick over the
        # per-(request, step) key streams — same [B]-ids-only transfer
        # contract as _argmax, and sampling is a pure function of
        # (seed, rid, step), so output cannot depend on admission
        # interleaving, scheduler policy, or batch composition.
        temp = cfg.temperature

        def sample_tokens(logits, seed, rids, steps):
            base = jax.random.PRNGKey(seed)

            def one(row_logits, rid, step):
                k = jax.random.fold_in(jax.random.fold_in(base, rid), step)
                return jax.random.categorical(k, row_logits / temp)

            return jax.vmap(one)(logits, rids, steps).astype(jnp.int32)

        self._sample_tokens = jax.jit(sample_tokens) if temp > 0 else None
        self._splice = None     # built lazily (needs the cache axis probe)
        # ---- speculative decoding (cfg.spec) ----
        if cfg.spec is not None:
            draft = cfg.spec.draft

            # the drafter's cache is not donated, so its one-token step
            # keeps the layers' own write: verify_step at one token, the
            # arithmetic of decode_step
            def drafter_step(p, tokens, cache):
                logits, cache = draft.verify_step(p, tokens, cache)
                return logits[:, 0], cache

            def drafter_prefill_padded(p, toks, lens):
                return draft.prefill_padded(
                    p, {"tokens": toks, "lengths": lens}, cfg.max_len, kvd)

            self._verify = jax.jit(model.verify_step)
            self._draft_decode = jax.jit(drafter_step)
            self._draft_prefill_padded = jax.jit(drafter_prefill_padded)
            # rollback: rewrite per-row cache lengths from the host-
            # tracked accepted lengths (pure truncation — rejected
            # positions stay masked garbage until overwritten)
            self._set_lens = jax.jit(Model.override_cache_lengths)
            self._draft_splice = None   # lazy (drafter cache axis probe)
        # the serve cache backend persists across serve() calls so the
        # prefix trie and page pool survive request churn; reset_cache()
        # drops it explicitly
        self._backend = None
        # ScheduleStats of each slot-refill / admission pass (see serve())
        self.refill_stats: list = []
        self.last_report: Optional[ServeReport] = None

    def _decode(self, params, tokens, cache):
        """One decode step, ``jit_decode_step``.  A cache the model writes
        in place (``Model.writes_in_place``: the contiguous serve cache,
        a padded prefill's) is donated, so the step writes only the new
        tokens into it and the caller must rebind the returned cache; any
        other (paged pool, scalar lengths) is kept."""
        step = (self._decode_in_place if self.model.writes_in_place(cache)
                else self._decode_kept)
        return step(params, tokens, cache)

    def reset_cache(self) -> None:
        """Drop the persistent serve cache backend (page pool, prefix
        trie, KV pages); the next ``serve()`` call builds a fresh one."""
        self._backend = None

    # ------------------------------------------------------------- sampling
    #
    # Every sampled token is a pure function of (seed, rid, step):
    # key = fold_in(fold_in(PRNGKey(seed), rid), step).  generate() and
    # both serve modes draw from the same streams, so temperature > 0
    # output is invariant to admission interleaving, scheduler policy,
    # slot count, and batch composition — the same serve == generate
    # differential greedy decoding has always had.

    def _pick(self, logits, seed, rids, step):
        """Next token for every row ([B,V] logits -> [B] ids, one
        transfer).  ``step`` may be a scalar (generate: all rows at the
        same step) or a [B] vector (continuous: each slot at its own
        output length)."""
        if self.cfg.temperature <= 0.0:
            return self._argmax(logits)
        b = logits.shape[0]
        steps = jnp.broadcast_to(jnp.asarray(step, jnp.int32), (b,))
        return self._sample_tokens(logits, seed,
                                   jnp.asarray(rids, jnp.int32), steps)

    def _sample_row(self, logits_row, seed, rid, step) -> int:
        """One slot's next token (row logits [V]) — the admission-time
        single-row case, same (seed, rid, step) stream as _pick."""
        if self.cfg.temperature <= 0.0:
            return int(jnp.argmax(logits_row))
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(seed), rid), step)
        return int(jax.random.categorical(
            key, logits_row / self.cfg.temperature))

    # ------------------------------------------------------------- generate

    def generate(
        self,
        batch: dict,
        max_new_tokens: int,
        *,
        seed: int = 0,
        live: Optional[np.ndarray] = None,
        lengths: Optional[np.ndarray] = None,
        rids: Optional[Sequence[int]] = None,
    ) -> np.ndarray:
        """batch: family-appropriate dict with "tokens" [B, S_prompt].
        Returns generated tokens [B, max_new_tokens] (eos-padded).

        ``live``: optional [B] bool mask; False rows (padding slots) start
        done, so they emit eos only and never defeat the early-exit.

        ``lengths``: optional [B] true prompt lengths for right-padded
        mixed-length batches (pad-masked prefill + per-row cache
        positions); None keeps the uniform-width prefill.

        ``rids``: optional [B] request ids naming each row's sampling
        stream (temperature > 0 draws key fold_in(seed, rid, step)); None
        uses row indices.  Rows with the same (seed, rid) sample the same
        stream regardless of batch composition — this is what makes serve
        output match per-request generate() at temperature > 0."""
        if lengths is None:
            logits, cache = self._prefill(self.params, batch)
        else:
            logits, cache = self._prefill_padded(
                self.params, batch["tokens"],
                jnp.asarray(lengths, jnp.int32))
        b = batch["tokens"].shape[0]
        rids_arr = (np.arange(b, dtype=np.int32) if rids is None
                    else np.asarray(rids, np.int32))
        out = np.full((b, max_new_tokens), self.cfg.eos_id, np.int32)
        done = (np.zeros((b,), bool) if live is None
                else ~np.asarray(live, bool))
        tok = self._pick(logits, seed, rids_arr, 0)
        for t in range(max_new_tokens):
            out[:, t] = np.where(done, self.cfg.eos_id, np.asarray(tok))
            done |= np.asarray(tok) == self.cfg.eos_id
            if done.all():
                break
            logits, cache = self._decode(self.params, tok[:, None], cache)
            tok = self._pick(logits, seed, rids_arr, t + 1)
        return out

    # ---------------------------------------------------------------- serve

    def serve(
        self,
        prompts: Sequence,
        max_new_tokens: int,
        *,
        seed: int = 0,
    ) -> list:
        """Serve an arbitrary number of requests through ``cfg.slots`` fixed
        batch slots under ``cfg.mode``; returns one generated token array
        per request, in submission order (eos-padded to each request's
        token budget).

        ``prompts``: 1-D int arrays, or :class:`repro.serve.queue.Request`
        objects (which may carry a per-request ``max_new_tokens``).
        Admission / refill-packing runs under the scheduler named by
        ``cfg.refill_schedule``; its :class:`ScheduleStats` accumulate in
        ``self.refill_stats`` and the run's full latency/throughput
        telemetry lands in ``self.last_report``.
        """
        if self.cfg.slots < 1:
            raise ValueError(f"ServeConfig.slots must be >= 1, "
                             f"got {self.cfg.slots}")
        if self.model.cfg.family not in _SERVABLE:
            raise ValueError(
                f"serve() handles token-only families {_SERVABLE}; "
                f"{self.model.cfg.family!r} needs modal inputs — "
                f"use generate() directly")
        if max_new_tokens < 0:
            raise ValueError(f"max_new_tokens must be >= 0, "
                             f"got {max_new_tokens}")
        if self.cfg.on_pressure not in ("raise", "shed", "defer"):
            raise ValueError(
                f"ServeConfig.on_pressure must be 'raise', 'shed' or "
                f"'defer', got {self.cfg.on_pressure!r}")
        if self.cfg.max_retries < 0:
            raise ValueError(f"ServeConfig.max_retries must be >= 0, "
                             f"got {self.cfg.max_retries}")
        if self.cfg.deadline_ticks is not None and self.cfg.deadline_ticks < 1:
            raise ValueError(f"ServeConfig.deadline_ticks must be >= 1, "
                             f"got {self.cfg.deadline_ticks}")
        spec_k = 0
        spec = self.cfg.spec
        if spec is not None:
            # speculation preconditions fail fast, like the moe/MLA paged
            # and quantized rejects: rollback is a pure length truncation,
            # so both models must be dense non-MLA, share a vocab, and
            # decode greedily (acceptance compares argmax streams)
            if self.cfg.mode != "continuous":
                raise ValueError(
                    "ServeConfig.spec needs mode='continuous' (the rounds "
                    "barrier has no per-slot decode loop to speculate in)")
            if self.cfg.temperature > 0:
                raise ValueError(
                    "speculative decoding is greedy-only: acceptance "
                    "compares draft/target argmax streams — set "
                    "temperature=0 or spec=None")
            for m, role in ((self.model, "target"), (spec.draft, "draft")):
                if not m.supports_speculation:
                    raise ValueError(
                        f"{role} model {m.cfg.name!r} "
                        f"(family={m.cfg.family}"
                        f"{', MLA' if m.cfg.use_mla else ''}) cannot "
                        f"speculate: rollback needs every cache leaf to "
                        f"be a length-masked KV cache (dense, non-MLA)")
            if spec.draft.cfg.vocab_size != self.model.cfg.vocab_size:
                raise ValueError(
                    f"draft vocab ({spec.draft.cfg.vocab_size}) != target "
                    f"vocab ({self.model.cfg.vocab_size}) — acceptance "
                    f"compares token ids, the vocabularies must match")
            spec_k = self._spec_k()
            if spec_k < 0:
                raise ValueError(f"SpecConfig.k must be >= 0, got {spec_k}")
        requests = as_requests(prompts)
        for r in requests:
            budget = (max_new_tokens if r.max_new_tokens is None
                      else min(r.max_new_tokens, max_new_tokens))
            if r.prompt_len + budget > self.cfg.max_len:
                raise ValueError(
                    f"request {r.rid}: prompt ({r.prompt_len}) + token "
                    f"budget ({budget}) exceeds max_len "
                    f"{self.cfg.max_len} — the cache would overflow")
            if spec_k and r.prompt_len + budget + spec_k - 1 > self.cfg.max_len:
                raise ValueError(
                    f"request {r.rid}: prompt ({r.prompt_len}) + budget "
                    f"({budget}) + draft span ({spec_k}) - 1 exceeds "
                    f"max_len {self.cfg.max_len} — a verify step near the "
                    f"budget would write past the cache; shrink k or "
                    f"leave k tokens of headroom")
        if self.cfg.cache != "contiguous" and self.cfg.mode != "continuous":
            raise ValueError(
                f"cache={self.cfg.cache!r} needs mode='continuous' "
                f"(the rounds barrier has no slot lifecycle to page)")
        with _span("serve.call", requests=len(requests),
                   slots=self.cfg.slots):
            if self.cfg.mode == "continuous":
                return self._serve_continuous(requests, max_new_tokens,
                                              seed)
            if self.cfg.mode == "rounds":
                return self._serve_rounds(requests, max_new_tokens, seed)
        raise ValueError(f"unknown serve mode {self.cfg.mode!r}")

    # ------------------------------------------------- continuous batching

    def _bucket_width(self, prompt_len: int) -> int:
        """Prefill width for a prompt: the enclosing bucket where padding
        is safe (one jit specialization per bucket), the exact length
        where it is not (one per distinct length)."""
        cfg = self.cfg
        if prompt_len > cfg.max_len:
            raise ValueError(f"prompt length {prompt_len} exceeds "
                             f"max_len {cfg.max_len}")
        if not self.model.pad_safe_prefill:
            return prompt_len
        if cfg.prefill_buckets:
            for w in sorted(cfg.prefill_buckets):
                if w >= prompt_len:
                    return min(int(w), cfg.max_len)
            raise ValueError(
                f"prompt length {prompt_len} exceeds the largest prefill "
                f"bucket {max(cfg.prefill_buckets)}")
        w = 8
        while w < prompt_len:
            w *= 2
        return min(w, cfg.max_len)

    def _ensure_splice(self):
        if self._splice is None:
            model = self.model
            axes = model.cache_batch_axes(dtype=self.kv_dtype)

            def splice_cache(c, pc, s):
                return model.splice_cache(c, pc, s, axes=axes)

            self._splice = jax.jit(splice_cache)

    def _ensure_draft_splice(self):
        if self._draft_splice is None:
            draft = self.cfg.spec.draft
            axes = draft.cache_batch_axes(dtype=self.kv_dtype)

            def drafter_splice(c, pc, s):
                return draft.splice_cache(c, pc, s, axes=axes)

            self._draft_splice = jax.jit(drafter_splice)

    def _spec_k(self) -> int:
        """Resolved draft span: explicit SpecConfig.k, or the calibrated
        grain choice (TuningContext.draft_span — mirroring how
        admission_block resolves when ServeConfig.admission_block is
        None).  0 disables speculation for the call."""
        spec = self.cfg.spec
        if spec is None:
            return 0
        if spec.k is not None:
            return spec.k
        return rt.tuning().draft_span()

    def _serve_continuous(self, requests: List[Request],
                          max_new_tokens: int, seed: int) -> list:
        cfg = self.cfg
        # fault injection resolves once per serve() call: a single module-
        # global read when no plan is installed (zero-overhead contract)
        inj = _faults.active()
        tok = np.zeros(cfg.slots, np.int32)
        slot_req: List[Optional[Request]] = [None] * cfg.slots
        slot_cap = np.zeros(cfg.slots, np.int64)
        outputs: List[Optional[list]] = [None] * len(requests)
        # ---- speculative state (inert when spec_k == 0) ----
        spec = cfg.spec
        spec_k = self._spec_k()
        draft_cache = None
        # host mirror of each slot's cache length (prompt + emitted - 1:
        # the last emitted token is never consumed until the next tick) —
        # the rollback source after each verify advances every row by the
        # full draft span.  Shared by target and drafter, whose consumed
        # streams are identical by construction.
        slot_len = np.zeros(cfg.slots, np.int32)
        drafted_total = 0
        accepted_total = 0
        degraded_ticks = 0
        decode_slot_ticks = 0
        if spec_k:
            self._ensure_draft_splice()
            draft_cache = spec.draft.set_cache_lengths(
                spec.draft.init_cache(cfg.slots, cfg.max_len,
                                      self.kv_dtype),
                np.zeros(cfg.slots, np.int32))
        telem = {r.rid: RequestTelemetry(rid=r.rid,
                                         prompt_len=r.prompt_len)
                 for r in requests}
        tick = 0
        # rid of a request past the cfg.max_deferred_ticks aging bound:
        # while set, admission is barred for everyone else (see below)
        starving: Optional[int] = None
        # ---- degradation state (inert on the no-fault default path) ----
        terminal: set = set()            # rids holding a terminal status
        not_before: Dict[int, int] = {}  # retry backoff: rid -> earliest tick
        engine_stall_s = 0.0             # injected decode-loop stall ledger

        def cap_of(req: Request) -> int:
            return (max_new_tokens if req.max_new_tokens is None
                    else min(req.max_new_tokens, max_new_tokens))

        from repro.serve.paged_cache import make_cache_backend
        # reuse the persistent backend: the prefix trie and page pool must
        # survive request churn across serve() calls (rebuilding per call
        # silently discarded every cached prefix).  begin_call() re-arms
        # the per-call report window; reset_cache() forces a rebuild.
        if self._backend is None or self._backend.name != cfg.cache:
            self._backend = make_cache_backend(self)
        backend = self._backend
        with _span("serve.plan"):
            block = cfg.admission_block
            if block is None:
                block = rt.tuning().admission_block(len(requests),
                                                    cfg.slots)
            queue = RequestQueue(requests, cfg.slots, cfg.refill_schedule,
                                 block_size=block)
            backend.begin_call()
        self.refill_stats = [queue.plan.stats]
        backend.validate(requests, cap_of)
        for req in requests:
            # configuration errors (over-bucket / over-max_len prompts)
            # fail fast here, like backend.validate — isolation is for
            # per-request runtime faults, not caller mistakes
            self._bucket_width(req.prompt_len)
        t0 = time.monotonic()

        def set_terminal(rid: int, status: str, reason: str = "") -> None:
            """Assign the request's terminal status.  Exactly once by
            construction — a second assignment is an engine accounting bug
            and raises (the chaos differential's no-lost-request half is
            checked at the end of the run)."""
            nonlocal starving
            if rid in terminal:
                raise RuntimeError(
                    f"request {rid} assigned a second terminal status "
                    f"({telem[rid].status!r} then {status!r})")
            terminal.add(rid)
            tm = telem[rid]
            tm.status = status
            tm.fail_reason = reason
            if tm.finish_tick < 0:
                tm.finish_tick = tick
            if not np.isfinite(tm.finish_s):
                tm.finish_s = time.monotonic() - t0
            if starving == rid:
                starving = None

        def retry_or_fail(req: Request, reason: str) -> bool:
            """A cancelled / poisoned request re-enters the admission race
            with exponential backoff (holding no slot while it waits) until
            its retry budget is spent, then goes terminal FAILED.  Returns
            True when the request was requeued for another attempt."""
            tm = telem[req.rid]
            if tm.retries < cfg.max_retries:
                tm.retries += 1
                delay = max(1, int(round(cfg.backoff * 2 ** (tm.retries - 1))))
                not_before[req.rid] = tick + delay
                queue.requeue(req.rid)
                return True
            set_terminal(req.rid, "failed", reason)
            return False

        def finish(slot: int) -> None:
            req = slot_req[slot]
            tm = telem[req.rid]
            tm.finish_tick = tick
            tm.finish_s = time.monotonic() - t0
            tm.decode_tokens = max(0, len(outputs[req.rid]) - 1)
            slot_req[slot] = None
            slot_len[slot] = 0
            backend.finish(slot)
            set_terminal(req.rid, "ok")

        def cancel(slot: int, reason: str) -> None:
            """Cancel mid-decode: reclaim the slot and its cache pages,
            discard the partial tokens, and retry or fail the request."""
            req = slot_req[slot]
            slot_req[slot] = None
            slot_len[slot] = 0
            backend.finish(slot)
            outputs[req.rid] = None
            retry_or_fail(req, reason)

        tick_end_s: List[float] = []
        while True:
            # nothing left: leave before a tick span opens, so that each
            # serve.tick span is one tick (the check below stays for
            # passes that empty the queue without decoding)
            if queue.pending == 0 and all(r is None for r in slot_req):
                break
            with _span("serve.tick", tick=tick):
                # refill every free slot in flight — no round barrier, so
                # a long sequence elsewhere never blocks this admission
                progress = False
                deferred_pass = 0   # admissions bounced on page pressure
                delayed_pass = 0    # requests held out by retry backoff
                for s in range(cfg.slots):
                    if slot_req[s] is not None:
                        continue
                    nxt = queue.next_for(s)
                    if nxt is None:
                        continue
                    req, stolen = nxt
                    if cap_of(req) < 1:  # zero token budget: nothing to do
                        outputs[req.rid] = []
                        telem[req.rid].admit_tick = tick
                        telem[req.rid].finish_tick = tick
                        telem[req.rid].finish_s = time.monotonic() - t0
                        set_terminal(req.rid, "ok")
                        progress = True
                        continue
                    if not_before.get(req.rid, 0) > tick:
                        # retry backoff: not yet eligible — rotate to the
                        # back of the shallowest backlog (no deferral
                        # penalty) so it cannot head-of-line block the
                        # slot it landed on
                        queue.requeue(req.rid)
                        delayed_pass += 1
                        continue
                    if starving is not None and req.rid != starving:
                        # aging barrier: a request past the deferral bound
                        # is waiting on pages, and every small admission
                        # here would snatch them first — steady churn then
                        # defers the large request forever.  Hold this
                        # slot empty (re-queue, no deferral penalty) until
                        # the starving request lands; running slots drain
                        # and free pages.
                        queue.push_back(s, req)
                        continue
                    with _span("serve.admit", rid=req.rid, slot=s,
                               prompt_len=req.prompt_len):
                        try:
                            if inj is not None:
                                inj.check_admission(req.rid)
                            res = backend.admit(s, req, cap_of(req))
                        except Exception as e:
                            if not cfg.isolate_failures:
                                raise
                            # per-request failure isolation: this admission
                            # died (a poisoned request, or an organic
                            # prefill error scoped to it) — the batch
                            # survives.  The backend reclaims any pages it
                            # claimed before re-raising, so nothing leaks;
                            # the request retries or goes FAILED.
                            if retry_or_fail(
                                    req,
                                    f"admission: {type(e).__name__}: {e}"):
                                delayed_pass += 1
                            else:
                                progress = True
                            continue
                        if res is None:
                            # partial admission: the request's page demand
                            # exceeds the free pool right now — back on
                            # this slot's backlog (still next in its claim
                            # order), retry once decode ticks free pages
                            queue.push_back(s, req)
                            tm = telem[req.rid]
                            tm.deferred_ticks += 1
                            deferred_pass += 1
                            if (starving is None
                                    and cfg.max_deferred_ticks is not None
                                    and tm.deferred_ticks
                                    > cfg.max_deferred_ticks):
                                starving = req.rid
                            continue
                        progress = True
                        if req.rid == starving:
                            starving = None
                        first = self._sample_row(res.logits_row, seed,
                                                 req.rid, 0)
                        slot_req[s] = req
                        slot_cap[s] = cap_of(req)
                        slot_len[s] = req.prompt_len
                        tok[s] = first
                        outputs[req.rid] = [first]
                        if spec_k:
                            # the drafter consumes the same prompt into its
                            # own contiguous cache row (its proposals must
                            # continue exactly the target's stream)
                            w = self._bucket_width(req.prompt_len)
                            dtoks = np.zeros((1, w), np.int32)
                            dtoks[0, : req.prompt_len] = req.prompt
                            _, dcache = self._draft_prefill_padded(
                                spec.draft_params, jnp.asarray(dtoks),
                                jnp.asarray([req.prompt_len], jnp.int32))
                            draft_cache = self._draft_splice(
                                draft_cache, dcache,
                                jnp.asarray(s, jnp.int32))
                    tm = telem[req.rid]
                    tm.admit_tick = tick
                    tm.ttft_s = time.monotonic() - t0
                    tm.stolen = stolen
                    tm.prefill_tokens = res.prefill_tokens
                    tm.prefix_hit_tokens = res.prefix_hit_tokens
                    if first == cfg.eos_id or slot_cap[s] <= 1:
                        finish(s)

                live = [s for s in range(cfg.slots)
                        if slot_req[s] is not None]
                if not live and queue.pending == 0:
                    break
                if not live:
                    if progress:
                        continue    # every admitted request finished on
                                    # its first token; loop back for the rest
                    if delayed_pass:
                        # everything actionable is waiting out a retry
                        # backoff and nothing is running: only the clock
                        # can move, so charge an idle tick and retry
                        # admission
                        tick += 1
                        continue
                    # true admission deadlock: nothing running, nothing
                    # admitted, and no decode tick can free pages —
                    # retrying is a spin.  cfg.on_pressure picks the blast
                    # radius.
                    if cfg.on_pressure == "shed":
                        # load shedding: drop the youngest request already
                        # bounced on pressure (max rid = latest submission
                        # — the oldest deferred request keeps its aging
                        # credit), then let the survivors admit into the
                        # freed demand
                        pend = queue.pending_rids()
                        deferred = [r for r in pend
                                    if telem[r].deferred_ticks > 0]
                        victim = max(deferred) if deferred else max(pend)
                        queue.drop(victim)
                        set_terminal(victim, "shed",
                                     "load shed: admission deadlock under "
                                     "page pressure")
                        continue
                    if cfg.on_pressure == "defer":
                        # graceful completion: requests that can never
                        # admit go terminal FAILED and the batch ends
                        # around them
                        for r in list(queue.pending_rids()):
                            queue.drop(r)
                            set_terminal(r, "failed",
                                         "page pressure: admission can "
                                         "never proceed")
                        continue
                    # "raise" — the pre-robustness behavior, still the
                    # default
                    raise RuntimeError(
                        f"refill deadlock: {queue.pending} request(s) "
                        f"pending, no slot live, and no admission can "
                        f"proceed")

                if inj is not None:
                    # injected decode-loop stall (a straggler engine
                    # tick): charged to the chaos clock and surfaced in
                    # the report's injected_stall_s — the exposed-wait term
                    engine_stall_s += inj.engine_stall(tick)
                # one unit of per-token decode bookkeeping per (live slot,
                # tick) — the serving analogue of the per-item FAA the
                # paper amortizes; speculation emits >1 token per unit
                decode_slot_ticks += len(live)
                with _span("serve.decode"):
                    if spec_k:
                        # ---- draft: k sequential batched drafter steps.
                        # Column 0 is each slot's last emitted (still
                        # unconsumed) token; columns 1..k are the
                        # drafter's greedy continuations.
                        draft_block = np.zeros((cfg.slots, spec_k + 1),
                                               np.int32)
                        draft_block[:, 0] = tok
                        dtok = jnp.asarray(tok)[:, None]
                        for j in range(1, spec_k + 1):
                            dlogits, draft_cache = self._draft_decode(
                                spec.draft_params, dtok, draft_cache)
                            dtok = self._argmax(dlogits)[:, None]
                            draft_block[:, j] = np.asarray(dtok)[:, 0]
                        # ---- verify all k+1 positions in one batched
                        # forward; greedy[s, j] is exactly the token a
                        # non-speculative decode tick would emit after
                        # consuming draft_block[s, :j+1] (per-position
                        # attention in attn_apply)
                        vlogits, backend.cache = self._verify(
                            self.params, jnp.asarray(draft_block),
                            backend.cache)
                        greedy = np.asarray(self._argmax(vlogits))
                    else:
                        logits, backend.cache = self._decode(
                            self.params, jnp.asarray(tok)[:, None],
                            backend.cache)
                        if cfg.temperature <= 0:
                            next_toks = np.asarray(self._argmax(logits))
                        else:
                            # batched per-(request, step) sampling: one
                            # transfer per tick ([B] ids), never a
                            # per-slot host sync
                            rids_b = np.zeros(cfg.slots, np.int32)
                            steps_b = np.zeros(cfg.slots, np.int32)
                            for s in live:
                                rids_b[s] = slot_req[s].rid
                                steps_b[s] = len(outputs[slot_req[s].rid])
                            next_toks = np.asarray(self._sample_tokens(
                                logits, seed, jnp.asarray(rids_b),
                                jnp.asarray(steps_b)))
                tick += 1
                tick_end_s.append(time.monotonic() - t0)
                with _span("serve.emit"):
                    if spec_k:
                        # ---- host acceptance: longest matching prefix +
                        # one corrected token, capped by remaining budget,
                        # cut at eos
                        decisions = {}
                        full_accept = False
                        for s in live:
                            rid = slot_req[s].rid
                            degraded = False
                            if inj is not None:
                                try:
                                    inj.check_draft(rid, len(outputs[rid]))
                                except Exception:
                                    if not cfg.isolate_failures:
                                        raise
                                    # poisoned draft: degrade this slot's
                                    # tick to non-speculative decode
                                    # (accept nothing, emit only the
                                    # corrected token) — the request
                                    # survives, it just loses the
                                    # amortization
                                    degraded = True
                            m = 0
                            if not degraded:
                                while (m < spec_k
                                       and int(draft_block[s, m + 1])
                                       == int(greedy[s, m])):
                                    m += 1
                            if m == spec_k:
                                full_accept = True
                            rem = int(slot_cap[s]) - len(outputs[rid])
                            emit = [int(t) for t in
                                    greedy[s, : min(m + 1, rem)]]
                            for ei, t in enumerate(emit):
                                if t == cfg.eos_id:
                                    emit = emit[: ei + 1]
                                    break
                            decisions[s] = (emit, degraded)
                        if full_accept:
                            # resync: a fully accepted row's drafter never
                            # consumed its own k-th proposal; one extra
                            # batched step feeds it (the length rollback
                            # right below masks this step for every other
                            # row)
                            _, draft_cache = self._draft_decode(
                                spec.draft_params,
                                jnp.asarray(draft_block[:, -1:]),
                                draft_cache)
                        for s, (emit, _) in decisions.items():
                            slot_len[s] += len(emit)
                        # ---- rollback: both caches truncate to the
                        # accepted lengths; rejected positions become
                        # masked garbage (exactly zero attention weight)
                        # until overwritten
                        lens = jnp.asarray(slot_len, jnp.int32)
                        backend.cache = self._set_lens(backend.cache, lens)
                        draft_cache = self._set_lens(draft_cache, lens)
                        for s in live:
                            rid = slot_req[s].rid
                            emit, degraded = decisions[s]
                            tm = telem[rid]
                            tm.drafted_tokens += spec_k
                            tm.accepted_tokens += len(emit) - 1
                            drafted_total += spec_k
                            accepted_total += len(emit) - 1
                            if degraded:
                                degraded_ticks += 1
                            if inj is not None:
                                cancelled = False
                                base = len(outputs[rid])
                                for off in range(len(emit)):
                                    try:
                                        inj.check_decode(rid, base + off)
                                    except Exception as e:
                                        if not cfg.isolate_failures:
                                            raise
                                        cancel(s, f"decode: "
                                                  f"{type(e).__name__}: {e}")
                                        cancelled = True
                                        break
                                if cancelled:
                                    continue
                            outputs[rid].extend(emit)
                            tok[s] = emit[-1]
                            if (emit[-1] == cfg.eos_id
                                    or len(outputs[rid]) >= slot_cap[s]):
                                finish(s)
                    else:
                        for s in live:
                            rid = slot_req[s].rid
                            if inj is not None:
                                try:
                                    inj.check_decode(rid, len(outputs[rid]))
                                except Exception as e:
                                    if not cfg.isolate_failures:
                                        raise
                                    cancel(s, f"decode: "
                                              f"{type(e).__name__}: {e}")
                                    continue
                            nxt_tok = int(next_toks[s])
                            tok[s] = nxt_tok
                            outputs[rid].append(nxt_tok)
                            if (nxt_tok == cfg.eos_id
                                    or len(outputs[rid]) >= slot_cap[s]):
                                finish(s)
                    if cfg.deadline_ticks is not None:
                        for s in range(cfg.slots):
                            req = slot_req[s]
                            if req is None:
                                continue
                            if (tick - telem[req.rid].admit_tick
                                    >= cfg.deadline_ticks):
                                cancel(s, f"deadline: exceeded "
                                          f"{cfg.deadline_ticks} decode "
                                          f"tick(s) since admission")

        missing = [r.rid for r in requests if r.rid not in terminal]
        if missing:
            raise RuntimeError(
                f"lost request(s) {missing}: the run ended with no "
                f"terminal status assigned — engine accounting bug")
        results = []
        for req in requests:
            cap = cap_of(req)
            arr = np.full(cap, cfg.eos_id, np.int32)
            toks_r = outputs[req.rid] or []
            arr[: len(toks_r)] = toks_r
            results.append(arr)
        self.last_report = ServeReport(
            schedule=queue.plan.stats.schedule,
            mode="continuous",
            slots=cfg.slots,
            n_requests=len(requests),
            total_ticks=tick,
            wall_s=time.monotonic() - t0,
            total_tokens=int(sum(len(o) for o in outputs if o)),
            admission=queue.plan.stats,
            admission_steals=queue.steals,
            requests=[telem[r.rid] for r in requests],
        )
        self.last_report.prefill_tokens = int(
            sum(t.prefill_tokens for t in telem.values()))
        backend.fill_report(self.last_report)
        rep = self.last_report
        rep.failed_requests = sum(
            1 for t in telem.values() if t.status == "failed")
        rep.shed_requests = sum(
            1 for t in telem.values() if t.status == "shed")
        rep.retries = sum(t.retries for t in telem.values())
        rep.injected_stall_s = (
            engine_stall_s + queue.plan.stats.injected_stall_s
            + sum(st.injected_stall_s for st in rep.page_alloc_stats))
        rep.spec_k = spec_k
        rep.drafted_tokens = drafted_total
        rep.accepted_tokens = accepted_total
        rep.draft_degraded_ticks = degraded_ticks
        rep.decode_slot_ticks = decode_slot_ticks
        rep.tick_end_s = tick_end_s
        return results

    # --------------------------------------------- legacy round barrier

    def _serve_rounds(self, requests: List[Request],
                      max_new_tokens: int, seed: int) -> list:
        """Round-barrier fallback: cohorts of up to ``slots`` requests in
        submission order.  Pad-masked prefill admits mixed widths into one
        cohort, so a short cohort no longer strands free slots while
        different-length requests wait (the old head-of-line hazard)."""
        cfg = self.cfg
        pending = list(requests)
        results: list = [None] * len(requests)
        self.refill_stats = []
        telem = {r.rid: RequestTelemetry(rid=r.rid,
                                         prompt_len=r.prompt_len)
                 for r in requests}
        t0 = time.monotonic()
        tick = 0
        total_tokens = 0
        while pending:
            if self.model.pad_safe_prefill:
                # the head-of-line fix: any slots consecutive requests form
                # a cohort — pad-masked prefill batches mixed widths safely
                round_reqs = pending[: cfg.slots]
                pending = pending[cfg.slots:]
                width = self._bucket_width(
                    max(r.prompt_len for r in round_reqs))
            else:
                # padding would run through the recurrent state / expert
                # router, so cohorts stay same-length (the seed behavior)
                width = pending[0].prompt_len
                round_reqs = [r for r in pending
                              if r.prompt_len == width][: cfg.slots]
                taken = {r.rid for r in round_reqs}
                pending = [r for r in pending if r.rid not in taken]
            caps = [(max_new_tokens if r.max_new_tokens is None
                     else min(r.max_new_tokens, max_new_tokens))
                    for r in round_reqs]
            round_new = max(caps)
            # pad to the full slot count so the batch shape is constant per
            # width bucket; unused slots carry zeros and are dropped below.
            tokens = np.zeros((cfg.slots, width), np.int32)
            lengths = np.ones(cfg.slots, np.int32)

            def pack(j: int) -> None:
                r = round_reqs[j]
                tokens[j, : r.prompt_len] = r.prompt
                lengths[j] = r.prompt_len

            self.refill_stats.append(pf.parallel_for_stats(
                pack, len(round_reqs),
                n_threads=max(1, min(cfg.refill_threads, len(round_reqs))),
                schedule=cfg.refill_schedule, block_size=1, layer="serve"))
            # each row samples its request's own (seed, rid, step) stream,
            # so rounds-mode temperature output matches per-request
            # generate() and the continuous mode exactly (padding rows
            # reuse rid 0; they start dead and never emit)
            live = np.arange(cfg.slots) < len(round_reqs)
            rids = [r.rid for r in round_reqs]
            rids += [0] * (cfg.slots - len(rids))
            out = self.generate({"tokens": tokens}, round_new,
                                seed=seed, live=live,
                                lengths=lengths, rids=rids)
            now = time.monotonic() - t0
            for j, r in enumerate(round_reqs):
                arr = out[j][: caps[j]].copy()  # eos-padded by generate()
                results[r.rid] = arr
                # emitted = up to and including the first (real) eos; the
                # rest of the row is padding — same accounting as the
                # continuous mode this baseline is benchmarked against
                hits = np.nonzero(arr == cfg.eos_id)[0]
                emitted = int(hits[0]) + 1 if hits.size else caps[j]
                tm = telem[r.rid]
                tm.admit_tick = tick
                tm.ttft_s = now  # round granularity: the barrier is the point
                tm.finish_s = now
                tm.finish_tick = tick + round_new
                tm.decode_tokens = max(0, emitted - 1)
                total_tokens += emitted
            tick += round_new
        self.last_report = ServeReport(
            schedule=cfg.refill_schedule
            if isinstance(cfg.refill_schedule, str)
            else getattr(cfg.refill_schedule, "name", "custom"),
            mode="rounds",
            slots=cfg.slots,
            n_requests=len(requests),
            total_ticks=tick,
            wall_s=time.monotonic() - t0,
            total_tokens=total_tokens,
            admission=self.refill_stats[0] if self.refill_stats else None,
            admission_steals=0,
            requests=[telem[r.rid] for r in requests],
        )
        return results
