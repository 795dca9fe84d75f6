"""LLM serving engine: continuous batching over a paged (or slot) KV cache.

One Engine == one SaaS "VM instance" in TAPAS terms.  It exposes the knobs
the Instance Configurator turns (paper Table 1): max batch size, frequency
cap (simulated via a step-time multiplier), model variant (size /
quantization — swap params), and reports goodput (tokens/s within TTFT/TBT
SLOs, SLO = 5x unloaded latency, paper §3.3).

Serving modes:

* ``paged`` (default for plain-GQA models) — KV lives in a global block
  pool (``PagedCachePool``); admission runs *bucketed batched prefill*
  (prompts padded to power-of-two length buckets, one jitted prefill per
  bucket shape instead of one trace per request) and decode walks
  per-request block tables.  When the pool runs out of blocks mid-decode
  the youngest request is preempted and recomputed later (vLLM-style).
* ``slots`` — the legacy contiguous-slot pool, kept for cache families the
  block pool cannot hold (MLA latent, SWA ring, recurrent state) and as
  the ground truth the paged path is tested against.

The paged decode hot path is device-resident end to end:

* **Horizon decode** (``horizon=N``) — greedy sampling, KV append,
  position advance and finished-flag computation are fused into one
  jitted ``lax.scan`` loop (``Model.decode_multi_paged``); the engine
  runs up to N decode steps per host sync and only reads the drained
  ``(tokens, emitted)`` horizon back.  Block-table / position /
  last-token buffers persist on device between launches
  (``PagedCachePool`` mirrors) instead of being re-uploaded every step.
* **Chunked prefill** (``prefill_chunk=C``) — long prompts are split into
  C-token chunks processed one per scheduler step and interleaved with
  decode, so a long prefill never blocks decode TBT for more than one
  chunk (Sarathi-style).
* **Prefix sharing** (``prefix_share=True``) — admission looks the
  prompt's full blocks up in the pool's content-hash index and reuses
  refcounted blocks written by earlier requests (shared system prompts
  are neither recomputed nor double-stored).

Every ``step()`` is one ``engine.step`` profiler span
(``jax.profiler.StepTraceAnnotation``) with children ``engine.housekeeping``,
``engine.admit``, ``engine.prefill`` and ``engine.decode``; the last two
split into ``.prepare`` (host arrays and uploads), ``.launch`` (the jitted
call's dispatch), ``.sync`` (the device->host readback) and ``.commit``
(bookkeeping), under the same names in every serving mode; ``.launch``
carries ``attn``, the attention the decode program was built with
(``pallas`` or ``xla``), which ``EngineStats.decode_launches`` counts
without a profiler.  The profiler stamps them on the clock of its device
planes.  Span arguments are built only while a profiler session records;
without one a span costs about a microsecond.
"""
from __future__ import annotations

import functools
import heapq
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.configs.drafters import check_draft_pair
from repro.core.traces import _stable_seed
from repro.models.transformer import (SALT_SAMPLE, Model, event_keys,
                                      lane_keys, sample_from_dist,
                                      sampling_dist)
from repro.serving.kvcache import CachePool, PagedCachePool
from repro.serving.request import Request


@dataclass
class EngineKnobs:
    """The TAPAS-configurable instance settings."""
    max_batch: int = 8
    freq_scale: float = 1.0      # 1.0 = nominal clock; <1 slows step time
    variant: str = "full"        # model-size / quantization variant key
    paused: bool = False         # drained during reconfiguration (§4.3)


@dataclass
class EngineStats:
    prefill_tokens: int = 0
    decode_tokens: int = 0
    prefill_batches: int = 0     # jitted prefill launches (not requests)
    preemptions: int = 0         # requests requeued for recompute (pool ran
    #                              dry, or displaced by a variant reload)
    variant_swaps: int = 0       # set_variant reloads (may preempt actives)
    shard_swaps: int = 0         # set_shards reconfigures (preempt actives)
    rejected: int = 0            # finished "rejected": contexts that can
    #                              never fit max_seq, or retry-exhausted
    host_syncs: int = 0          # device->host readbacks on the serving path
    decode_syncs: int = 0        # the subset issued by decode launches
    # speculative decode accounting: one verify pass emits a whole
    # accepted run, so tokens-per-pass (and tokens-per-sync) is the
    # speedup speculation buys, not the old one-pass-per-token identity
    draft_tokens: int = 0        # drafts proposed across verify passes
    accepted_tokens: int = 0     # drafts accepted (excludes bonus tokens)
    verify_passes: int = 0       # target verify passes (lane-rounds) run
    # resilience accounting: each counter tracks one recovery mechanism;
    # terminal outcomes live on the Request (mutually exclusive), these
    # count *events*, so retried can exceed the number of requests
    submitted: int = 0           # requests ever handed to submit()
    timed_out: int = 0           # requests evicted past their deadline_ms
    retried: int = 0             # re-queues via the bounded-retry path
    #                              (quarantine / crash recompute) — NOT
    #                              pool-exhaustion preemptions
    retry_exhausted: int = 0     # retry budget burned -> finished rejected
    quarantined: int = 0         # lanes pulled by the NaN/Inf KV guard
    guard_scans: int = 0         # pre-decode corruption scans launched
    crashes: int = 0             # crash() invocations survived
    n_steps: int = 0             # recorded (working) scheduler steps
    step_time_total: float = 0.0  # running sum of freq-scaled step times
    # admission: requests that claimed a lane, and their summed wait in
    # the queue on the host's monotonic clock (submit or re-queue -> claim)
    admitted: int = 0
    queue_wait_s: float = 0.0
    # decode launches by the attention their program was built with
    # (``Model.paged_decode_path``): the Pallas kernel or the XLA gather
    decode_launches: dict = field(
        default_factory=lambda: {"pallas": 0, "xla": 0})
    completed: list = field(default_factory=list)
    _good_acc: dict = field(default_factory=dict, repr=False)

    def record_step(self, dt: float) -> None:
        self.n_steps += 1
        self.step_time_total += dt

    def goodput(self, *, ttft_slo: float, tbt_slo: float) -> float:
        """Tokens/s over completed requests meeting both SLOs.

        Only requests that finished ``accepted`` count: a request that
        produced tokens, was preempted, and later timed out (or burned
        its retry budget) must not credit those tokens as served — the
        stats-drift bug class the terminal-outcome invariant pins.

        Incremental: each completed request is folded into the per-SLO
        accumulator exactly once, so repeated calls on a long-lived engine
        do not rescan the whole history.
        """
        key = (ttft_slo, tbt_slo)
        idx, good, t_max = self._good_acc.get(key, (0, 0, 1e-9))
        for r in self.completed[idx:]:
            t_max = max(t_max, r.finish_s or 0.0)
            if (r.outcome == "accepted" and (r.ttft() or 0) <= ttft_slo
                    and (r.tbt() or 0) <= tbt_slo):
                good += len(r.output)
        self._good_acc[key] = (len(self.completed), good, t_max)
        return good / t_max

    @property
    def accepted_per_sync(self) -> float:
        """Accepted draft tokens per decode sync — the free tokens each
        host round-trip carried on top of the one-per-pass baseline."""
        return self.accepted_tokens / max(self.decode_syncs, 1)

    @property
    def acceptance_rate(self) -> float:
        """Fraction of proposed draft tokens the target accepted."""
        return self.accepted_tokens / max(self.draft_tokens, 1)


def shard_compat(shards: int, cfg) -> str | None:
    """Why ``cfg`` cannot serve at ``shards``-way model parallelism, or
    None when it can.

    Sharded serving requires *identity pads* — head / kv-head / vocab /
    ffn counts that divide the shard degree — so params transfer verbatim
    between plans on ``set_shards`` and the KV pool stays at the real
    head count on every rank."""
    if shards <= 1:
        return None
    if cfg.n_kv_heads and cfg.n_kv_heads % shards:
        return (f"{cfg.name}: n_kv_heads={cfg.n_kv_heads} is not divisible "
                f"by shard degree {shards}")
    if cfg.n_heads % shards:
        return (f"{cfg.name}: n_heads={cfg.n_heads} is not divisible by "
                f"shard degree {shards}")
    if cfg.vocab_size % shards:
        return (f"{cfg.name}: vocab_size={cfg.vocab_size} is not divisible "
                f"by shard degree {shards}")
    if cfg.d_ff % shards:
        return (f"{cfg.name}: d_ff={cfg.d_ff} is not divisible by "
                f"shard degree {shards}")
    return None


def _bucket(n: int, lo: int = 16, hi: int | None = None) -> int:
    """Power-of-two prompt-length bucket (bounds distinct prefill shapes).

    ``hi`` clamps the bucket to the cache capacity — a context one past a
    power of two must not round up to a shape that can never be inserted.
    Callers must reject contexts longer than ``hi`` beforehand.
    """
    b = lo
    while b < n:
        b *= 2
    if hi is not None:
        b = min(b, hi)
    return b


class Engine:
    def __init__(self, model: Model, params: Any, *, max_seq: int = 512,
                 n_slots: int = 8, knobs: EngineKnobs | None = None,
                 paged: bool | None = None, block_size: int = 16,
                 n_blocks: int | None = None, horizon: int = 1,
                 prefill_chunk: int | None = None,
                 prefix_share: bool = False, spec_k: int = 4,
                 draft: str | None = None, ngram: int = 2, seed: int = 0):
        self.model = model
        self.variants: dict[str, tuple[Model, Any]] = {"full": (model, params)}
        self.knobs = knobs or EngineKnobs(max_batch=n_slots)
        self.max_seq = max_seq
        self.n_slots = n_slots
        self.block_size = block_size
        self.n_blocks = n_blocks
        self.paged = model.supports_paged if paged is None else paged
        if horizon < 1:
            raise ValueError(f"horizon must be >= 1, got {horizon}")
        self.horizon = horizon
        # prefix sharing rides on the chunked (in-pool) prefill path: a
        # shared head must be skipped, so the suffix is prefilled against
        # the pool; default to one whole-prompt-sized chunk when unset
        if prefix_share and prefill_chunk is None:
            prefill_chunk = max_seq
        self.prefill_chunk = prefill_chunk
        self.prefix_share = prefix_share
        if (prefill_chunk or prefix_share) and not self.paged:
            raise ValueError("chunked prefill / prefix sharing require the "
                             "paged serving mode")
        # speculative decode: ``draft`` picks the proposer ("ngram" =
        # prompt-lookup, or a model drafter registered via add_drafter);
        # spec_k drafts are verified per target pass.  draft=None keeps
        # the plain fused-horizon decode path, graph-for-graph.
        if draft is not None and not self.paged:
            raise ValueError("speculative decoding requires the paged "
                             "serving mode")
        if draft is not None and spec_k < 1:
            raise ValueError(f"spec_k must be >= 1 with a drafter, "
                             f"got {spec_k}")
        if draft is not None and draft != "ngram":
            raise ValueError("model drafters are registered via "
                             "add_drafter()/set_drafter(); the constructor "
                             "only accepts draft='ngram' or None")
        self.spec_k = spec_k
        self.draft_name = draft
        self.ngram = ngram
        self.seed = seed
        self.drafters: dict[str, tuple[Model, Any]] = {}
        self.queue: deque[Request] = deque()
        self.active: dict[int, Request] = {}
        self.prefilling: dict[int, Request] = {}
        self._prefill_pos: dict[int, int] = {}
        self._pending_waiter: int | None = None   # req deferred on a
        #                                           pending shared prefill
        # resilience state — all of it inert on the no-fault path: the
        # backoff heap stays empty, deadline eviction is gated on
        # _has_deadlines, and the NaN guard scan only runs while armed,
        # so fault-free streams and host_syncs are byte-identical to a
        # pre-hardening engine
        self.offline = False          # crash()ed and not yet restore()d
        self.slow_factor = 1.0        # stuck-slow fault: step-time stretch
        self.retry_backoff_s = 0.05   # base of the exponential re-queue
        #                               backoff (doubles per retry)
        self._guard_armed = False     # scan KV for NaN/Inf before decode
        self._delayed: list = []      # (not_before_s, seq, req) heap
        self._delay_seq = 0
        self._has_deadlines = False
        self._step_num = 0            # step() calls, the profiler's step_num
        self._claims: list = []       # (context, shared, wait_ns) per lane
        #                               claimed by the current admission
        self.stats = EngineStats()
        self._bind(model)

    def _make_pool(self) -> None:
        """(Re)create only the KV pool for the current model.  Crash
        recovery goes through here: a restart wipes cache state but keeps
        the jitted entry points — no retrace, just cold KV."""
        if self.paged:
            self.pool: Any = PagedCachePool(
                self.model, self.n_slots, self.max_seq,
                block_size=self.block_size, n_blocks=self.n_blocks)
            if self._spec_on and self.draft_name != "ngram":
                d_model, _ = self.drafters[self.draft_name]
                self.pool.attach_draft(d_model)
        else:
            self.pool = CachePool(self.model, self.n_slots, self.max_seq)

    def _bind(self, model: Model) -> None:
        """(Re)build pool + jitted entry points for the current model."""
        self.model = model
        if self.paged and not model.supports_paged:
            raise ValueError(f"{model.cfg.name} cannot serve paged "
                             f"(attn_kind={model.cfg.attn_kind!r})")
        if self.paged:
            self.pool: Any = PagedCachePool(
                model, self.n_slots, self.max_seq,
                block_size=self.block_size, n_blocks=self.n_blocks)
            self._prefill_jit = jax.jit(model.prefill_ragged)
            self._decode_multi_jit = jax.jit(
                model.decode_multi_paged,
                static_argnames=("num_steps", "max_len"),
                donate_argnums=(1,))
            self._prefill_chunk_jit = jax.jit(model.prefill_chunk_paged,
                                              donate_argnums=(1,))
        else:
            self.pool = CachePool(model, self.n_slots, self.max_seq)
            self._prefill_jit = jax.jit(model.prefill)
            self._decode_jit = jax.jit(model.decode_step)
        self._decode_attn = model.paged_decode_path(
            jax.tree.leaves(self.pool.cache)[0].dtype) if self.paged \
            else "xla"
        self._bind_spec()

    # -- speculative decode (drafter lifecycle) ----------------------------
    @property
    def _spec_on(self) -> bool:
        return self.paged and self.spec_k > 0 and self.draft_name is not None

    def _bind_spec(self) -> None:
        """(Re)build the speculative entry points for the current target
        model and drafter choice."""
        self._decode_spec_jit = None
        self._d_params = None
        self._draft_prefill_jit = None
        self._draft_chunk_jit = None
        if not self._spec_on:
            if self.paged:
                self.pool.detach_draft()
            return
        if self.draft_name == "ngram":
            d_model = None
            self.pool.detach_draft()
        else:
            d_model, d_params = self.drafters[self.draft_name]
            check_draft_pair(self.model.cfg, d_model.cfg)
            self.pool.attach_draft(d_model)
            self._d_params = d_params
            self._draft_prefill_jit = jax.jit(d_model.prefill_ragged)
            self._draft_chunk_jit = jax.jit(d_model.prefill_chunk_paged,
                                            donate_argnums=(1,))
        self._decode_spec_jit = jax.jit(
            functools.partial(Model.decode_spec_paged, self.model, d_model),
            static_argnames=("num_steps", "spec_k", "max_len", "ngram"),
            donate_argnums=(1, 3))

    def add_drafter(self, name: str, model: Model, params: Any) -> None:
        """Register a small same-tokenizer model as a drafter choice
        (pairing is validated: shared vocab + paged-servable)."""
        check_draft_pair(self.model.cfg, model.cfg)
        self.drafters[name] = (model, params)

    def set_drafter(self, name: str | None) -> None:
        """Switch the speculation proposer mid-flight: None (off),
        "ngram" (prompt-lookup), or a registered model drafter.

        In-flight requests keep their target KV — speculation only
        changes how candidate tokens are PROPOSED, never what the target
        accepts, so no preemption is needed.  A freshly attached model
        drafter starts with a cold draft cache; that costs acceptance
        rate until lanes turn over, not correctness.
        """
        if name == self.draft_name:
            return
        if name is not None and name != "ngram" and name not in self.drafters:
            raise KeyError(f"unknown drafter {name!r}")
        self.draft_name = name
        self._bind_spec()

    def _req_seed(self, req: Request) -> int:
        """Per-request deterministic sampling seed: the request's own, or
        a crc32 fold of (engine seed, req_id) — process-stable, so
        sampled replays reproduce across runs (the trace_seed idiom)."""
        if req.seed is not None:
            return int(req.seed) % (2 ** 31)
        return _stable_seed("request", self.seed, req.req_id) % (2 ** 31)

    def _next_from_prefill(self, logits, reqs: list, idx) -> np.ndarray:
        """Each row's first output token from its prefill logits: argmax,
        with sampled rows (temperature > 0) drawn from the warped
        distribution under the request's deterministic key.  Folding the
        token's absolute sequence index keeps resumes replay-stable; the
        all-greedy fast path is byte-identical to the old argmax."""
        v = self.model.cfg.vocab_size
        nxt = np.array(jnp.argmax(logits[:, :v], axis=-1))
        if not any(r.temperature > 0 for r in reqs):
            return nxt
        rows = len(reqs)
        temps = jnp.asarray([r.temperature for r in reqs], jnp.float32)
        tks = jnp.asarray([r.top_k for r in reqs], jnp.int32)
        seeds = jnp.asarray([self._req_seed(r) for r in reqs], jnp.int32)
        dist = sampling_dist(logits[:rows, :v], temps, tks)
        keys = event_keys(lane_keys(seeds),
                          jnp.asarray(idx[:rows], jnp.int32), SALT_SAMPLE)
        nxt[:rows] = np.asarray(sample_from_dist(keys, dist, temps <= 0.0))
        return nxt

    # -- variant management (model-size / quantization knob) --------------
    def add_variant(self, name: str, model: Model, params: Any) -> None:
        self.variants[name] = (model, params)

    def set_variant(self, name: str) -> None:
        """Reload a different model variant (costs a pause, paper §4.3).

        In-flight requests lose their KV state (the new variant's cache is
        a different shape) but are not dropped: they are preempted — blocks
        released, requeued at the front — and recomputed under the new
        variant, exactly like a pool-exhaustion preemption.  Setting the
        already-active variant is a no-op."""
        if name == self.knobs.variant:
            return
        model, params = self.variants[name]
        err = shard_compat(self.shards, model.cfg)
        if err is not None:
            # reject BEFORE preempting anything: an indivisible variant
            # must not cost in-flight work on its way to the ValueError
            raise ValueError(f"set_variant({name!r}) at shard degree "
                             f"{self.shards}: {err}")
        in_flight = set(self.active) | set(self.prefilling)
        if in_flight:
            # reverse-sorted so the front of the queue ends up in rid order
            self._preempt(sorted(in_flight, reverse=True))
        self.knobs.variant = name
        self.stats.variant_swaps += 1
        self._bind(model)

    def variant_compatible(self, name: str) -> bool:
        """Can ``set_variant(name)`` succeed at the current shard degree?
        (The DegradationLadder asks before walking its quantized rung.)"""
        if name not in self.variants:
            return False
        return shard_compat(self.shards, self.variants[name][0].cfg) is None

    @property
    def params(self):
        return self.variants[self.knobs.variant][1]

    # -- shard management (parallelism-degree knob) ------------------------
    @property
    def shards(self) -> int:
        """Current model-parallel degree of the serving plan."""
        plan = self.model.plan
        return plan.tp if plan.paged_pool_sharded(self.model.cfg) else 1

    def can_shard(self, n: int) -> str | None:
        """Why the engine cannot reconfigure to ``n``-way model
        parallelism (None = it can): paged mode, enough local devices,
        and every registered variant/drafter divides cleanly."""
        if n < 1:
            return f"shard degree must be >= 1, got {n}"
        if n == 1:
            return None
        if not self.paged:
            return "sharded serving requires the paged mode"
        if jax.device_count() < n:
            return f"need {n} devices, have {jax.device_count()}"
        for kind, reg in (("variant", self.variants),
                          ("drafter", self.drafters)):
            for name, (m, _) in reg.items():
                err = shard_compat(n, m.cfg)
                if err is not None:
                    return f"{kind} {name!r}: {err}"
        return None

    def set_shards(self, n: int) -> None:
        """Reconfigure the model-parallel degree (costs a pause, like a
        variant reload): preempt in-flight work, rebuild every registered
        model under the new plan, transfer params under the new
        shardings, and rebind.  Raises (without preempting) when
        ``can_shard`` objects."""
        if n == self.shards:
            return
        err = self.can_shard(n)
        if err is not None:
            raise ValueError(f"set_shards({n}): {err}")
        from repro.serving.spec import serving_plan  # local: import cycle
        plan = serving_plan(n, param_dtype=self.model.plan.param_dtype)
        in_flight = set(self.active) | set(self.prefilling)
        if in_flight:
            self._preempt(sorted(in_flight, reverse=True))

        def rebuild(m: Model, p):
            new_m = Model(m.cfg, plan)
            if plan.mesh is not None:
                new_p = jax.device_put(p, new_m.param_shardings())
            else:
                new_p = jax.device_put(p, jax.devices()[0])
            return new_m, new_p

        self.variants = {k: rebuild(m, p)
                         for k, (m, p) in self.variants.items()}
        self.drafters = {k: rebuild(m, p)
                         for k, (m, p) in self.drafters.items()}
        self.stats.shard_swaps += 1
        del self.pool   # free the old pool before _bind zeroes the new one
        self._bind(self.variants[self.knobs.variant][0])

    # -- request lifecycle -------------------------------------------------
    def submit(self, req: Request) -> None:
        self.stats.submitted += 1
        if req.deadline_ms is not None:
            self._has_deadlines = True
        self._enqueue(req)

    def _enqueue(self, req: Request, *, front: bool = False) -> None:
        """Put a request in the admission queue (at the back, or at the
        front for recompute) and start its queue wait."""
        req.queued_ns = time.perf_counter_ns()
        if front:
            self.queue.appendleft(req)
        else:
            self.queue.append(req)

    def _claim(self, req: Request, shared_tokens: int = 0) -> None:
        """A request leaves the queue for a lane: count its wait."""
        wait_ns = time.perf_counter_ns() - req.queued_ns
        self.stats.admitted += 1
        self.stats.queue_wait_s += wait_ns / 1e9
        self._claims.append((len(req.prompt) + len(req.output),
                             shared_tokens, wait_ns))

    @staticmethod
    def _context(req: Request) -> list:
        """Prefill context: prompt plus any tokens generated before a
        preemption (recompute-style resume)."""
        return list(req.prompt) + list(req.output)

    def _finish(self, req: Request, now: float, outcome: str) -> None:
        """The single terminal transition: stamp exactly one outcome
        (Request.finish raises on a double-finish), bump its counter,
        and append to the completed log.  Every serving path ends here,
        which is what makes the outcome audit exhaustive."""
        req.finish(now, outcome)
        if outcome == "timed_out":
            self.stats.timed_out += 1
        elif outcome == "rejected":
            self.stats.rejected += 1
        self.stats.completed.append(req)

    def _reject(self, req: Request, now: float) -> None:
        """A context that can never fit the cache (even after recompute
        growth) is finished empty instead of looping through admission."""
        self._finish(req, now, "rejected")

    def _activate(self, req: Request, tok: int, now: float) -> None:
        """Append the prefill token and either activate the request or, if
        it already hit its budget/eos (e.g. resumed right at the limit),
        finish it without occupying a decode lane."""
        req.output.append(tok)
        if req.first_token_s is None:
            req.first_token_s = now
        if self._spec_on:
            lane = self.pool.lane_of[req.req_id]
            self.pool.set_hist_token(lane, int(self.pool.lengths[lane]), tok)
        if (len(req.output) >= req.max_new_tokens
                or (req.eos_id is not None and tok == req.eos_id)):
            self._finish(req, now, "accepted")
            self.pool.release(req.req_id)
            return
        self.active[req.req_id] = req
        if self.paged:
            self.pool.set_last_token(self.pool.lane_of[req.req_id], tok)

    # -- resilience: deadlines, bounded retry, NaN quarantine, crash -------
    def _release_delayed(self, now: float) -> None:
        """Move backoff-delayed retries whose time has come back into the
        admission queue (no-op while the heap is empty)."""
        while self._delayed and self._delayed[0][0] <= now:
            _, _, req = heapq.heappop(self._delayed)
            self._enqueue(req)

    def _expire_deadlines(self, now: float) -> None:
        """Evict every request past its deadline — queued, backing off,
        prefilling, or active — so expired work never occupies a lane.
        Gated on _has_deadlines: engines that never saw a deadline_ms
        skip this entirely (no-fault parity)."""
        if not self._has_deadlines:
            return

        def expired(r: Request) -> bool:
            return r.deadline_s is not None and now >= r.deadline_s

        if any(expired(r) for r in self.queue):
            kept: deque[Request] = deque()
            for req in self.queue:
                if expired(req):
                    self._finish(req, now, "timed_out")
                else:
                    kept.append(req)
            self.queue = kept
        if any(expired(item[2]) for item in self._delayed):
            kept_d = []
            for item in self._delayed:
                if expired(item[2]):
                    self._finish(item[2], now, "timed_out")
                else:
                    kept_d.append(item)
            self._delayed = kept_d
            heapq.heapify(self._delayed)
        for rid in sorted((rid for rid, r in self.active.items()
                           if expired(r)), reverse=True):
            req = self.active.pop(rid)
            self.pool.release(rid)
            self._finish(req, now, "timed_out")
        for rid in sorted((rid for rid, r in self.prefilling.items()
                           if expired(r)), reverse=True):
            req = self.prefilling.pop(rid)
            del self._prefill_pos[rid]
            self.pool.release(rid)
            self._finish(req, now, "timed_out")

    def _requeue_for_retry(self, req: Request, now: float) -> None:
        """Bounded retry on the recompute path: re-queue with exponential
        backoff, or finish rejected once the budget is burned.  Distinct
        from _preempt — preemptions are scheduler churn (unlimited, no
        backoff), retries are fault recovery (bounded, backed off)."""
        if req.retries >= req.max_retries:
            self.stats.retry_exhausted += 1
            self._finish(req, now, "rejected")
            return
        req.retries += 1
        self.stats.retried += 1
        delay = self.retry_backoff_s * (2.0 ** (req.retries - 1))
        self._delay_seq += 1
        heapq.heappush(self._delayed, (now + delay, self._delay_seq, req))

    def _quarantine_scan(self, now: float) -> int:
        """Pre-decode NaN/Inf sweep over active lanes' KV blocks.  Only
        runs while armed (a fault injection just landed), so the no-fault
        path never pays the scan or its host sync.  Corrupted lanes are
        quarantined: blocks released, request re-queued for recompute via
        the bounded-retry path — the corrupted KV never feeds a decode
        launch, which is why recovered streams match fault-free ones."""
        if not (self._guard_armed and self.paged and self.active):
            return 0
        self._guard_armed = False
        mask = np.zeros(self.pool.n_lanes, bool)
        for rid in self.active:
            mask[self.pool.lane_of[rid]] = True
        bad = self.pool.bad_lanes(mask)
        self.stats.host_syncs += 1
        self.stats.guard_scans += 1
        bad_rids = sorted((rid for rid in self.active
                           if bad[self.pool.lane_of[rid]]), reverse=True)
        for rid in bad_rids:
            req = self.active.pop(rid)
            self.pool.scrub_lane(rid)     # never recycle poisoned blocks
            self.pool.release(rid)
            self.stats.quarantined += 1
            self._requeue_for_retry(req, now)
        return len(bad_rids)

    def inject_kv_corruption(self, rid: int, *, last_block: bool = False,
                             arm_guard: bool = True) -> None:
        """Fault hook: poison one of an active request's KV blocks with
        NaNs (oldest block by default — cold corruption; freshest with
        ``last_block`` — a NaN-logit burst) and arm the guard scan.
        ``arm_guard=False`` models an unguarded engine: the corruption
        stays and the next decode reads it."""
        if not self.paged:
            raise ValueError("KV corruption targets the paged pool")
        if rid not in self.active:
            raise KeyError(f"request {rid} is not active")
        lane = self.pool.lane_of[rid]
        n_written = max(1, int(self.pool.lengths[lane]))
        idx = (n_written - 1) // self.pool.block_size if last_block else 0
        self.pool.corrupt_lane(lane, block_idx=idx)
        if arm_guard:
            self._guard_armed = True

    def crash(self, now: float, *, drop: bool = False) -> list:
        """Simulate process death.  The engine goes offline (step() is a
        no-op until restore()) and all KV state is lost.  With
        ``drop=False`` unfinished work is re-queued for recompute after
        restart; with ``drop=True`` (recovery disabled) every unfinished
        request is returned un-finished — the silent loss the resilience
        audit exists to catch."""
        self.stats.crashes += 1
        self.offline = True
        dropped: list[Request] = []
        for rid in sorted(set(self.active) | set(self.prefilling),
                          reverse=True):
            req = self.active.pop(rid, None)
            if req is None:
                req = self.prefilling.pop(rid)
                del self._prefill_pos[rid]
            if drop:
                dropped.append(req)
            else:
                self._enqueue(req, front=True)
        if drop:
            dropped.extend(self.queue)
            self.queue.clear()
            dropped.extend(item[2] for item in self._delayed)
            self._delayed.clear()
        self._pending_waiter = None
        self._prefill_pos.clear()
        self._make_pool()
        return dropped

    def restore(self) -> None:
        """Bring a crashed engine back online (its queue survives; KV was
        already wiped by crash())."""
        self.offline = False

    def heartbeat(self) -> bool:
        """Liveness probe the watchdog polls each tick."""
        return not self.offline

    def take_unfinished(self) -> list:
        """Strip every unfinished request off this engine (watchdog
        drain onto siblings): in-flight KV released, queued and
        backoff-delayed work unhooked.  Returns requests sorted by
        req_id so re-homing is deterministic."""
        out = list(self.queue)
        self.queue.clear()
        out.extend(item[2] for item in self._delayed)
        self._delayed.clear()
        for rid in sorted(set(self.active) | set(self.prefilling),
                          reverse=True):
            req = self.active.pop(rid, None)
            if req is None:
                req = self.prefilling.pop(rid)
                del self._prefill_pos[rid]
            self.pool.release(rid)
            out.append(req)
        self._pending_waiter = None
        return sorted(out, key=lambda r: r.req_id)

    # -- span arguments: built only while a profiler session records ------
    def _step_args(self) -> dict:
        return dict(queue=len(self.queue), active=len(self.active),
                    prefilling=len(self.prefilling))

    def _admit_args(self) -> dict:
        return dict(requests=len(self._claims),
                    prompt_tokens=sum(c[0] for c in self._claims),
                    shared_tokens=sum(c[1] for c in self._claims),
                    queue_wait_ms=sum(c[2] for c in self._claims) / 1e6)

    @staticmethod
    def _prefill_args(rows: int, tokens: int, finished: int) -> dict:
        return dict(rows=rows, tokens=tokens, finished=finished)

    def _count_launch(self, launch) -> None:
        """Count a decode launch by its attention path, and name the path
        on its span while a profiler records."""
        self.stats.decode_launches[self._decode_attn] += 1
        if TraceAnnotation.is_enabled():
            launch.set_metadata(attn=self._decode_attn)

    def _decode_args(self, horizon: int, preempted: int) -> dict:
        lane_of = self.pool.lane_of if self.paged else self.pool.slot_of
        return dict(lanes=len(self.active),
                    ctx_tokens=int(sum(self.pool.lengths[lane_of[rid]]
                                       for rid in self.active)),
                    horizon=horizon, preempted=preempted)

    # -- admission and prefill ---------------------------------------------
    def _admit(self, now: float) -> None:
        """Claim lanes for queued requests (``engine.admit``)."""
        self._claims = []
        with TraceAnnotation("engine.admit") as span:
            if not self.paged:
                self._admit_slots(now)
            elif self.prefill_chunk:
                self._admit_chunked(now)
            else:
                self._admit_paged(now)
            if TraceAnnotation.is_enabled():
                span.set_metadata(**self._admit_args())

    def _admit_slots(self, now: float) -> None:
        """Slot admission: one prefill launch per request."""
        while (self.queue and self.pool.has_free()
               and len(self.active) < self.knobs.max_batch
               and not self.knobs.paused):
            if len(self._context(self.queue[0])) > self.max_seq - 1:
                self._reject(self.queue.popleft(), now)
                continue
            req = self.queue.popleft()
            self._claim(req)
            with TraceAnnotation("engine.prefill") as span:
                with TraceAnnotation("engine.prefill.prepare"):
                    prompt = jnp.asarray([self._context(req)], jnp.int32)
                    n = prompt.shape[1]
                if TraceAnnotation.is_enabled():
                    span.set_metadata(**self._prefill_args(1, n, 1))
                with TraceAnnotation("engine.prefill.launch"):
                    logits, cache = self._prefill_jit(self.params, prompt)
                with TraceAnnotation("engine.prefill.sync"):
                    tok = int(self._next_from_prefill(
                        logits, [req], np.asarray([n]))[0])
                with TraceAnnotation("engine.prefill.commit"):
                    self.stats.prefill_tokens += n
                    self.stats.prefill_batches += 1
                    self.stats.host_syncs += 1
                    self.pool.insert(req.req_id, cache, n)
                    self._activate(req, tok, now)

    def _admit_paged(self, now: float) -> None:
        """Batched admission: drain the queue into length buckets, one
        jitted prefill per bucket shape (not per request)."""
        batch: list[Request] = []
        # reserve lanes/blocks as the batch builds — can_admit alone would
        # double-count the free lists across requests admitted together
        lanes_left = len(self.pool.free_lanes)
        blocks_left = len(self.pool.free_blocks)
        while (self.queue and not self.knobs.paused
               and len(self.active) + len(batch) < self.knobs.max_batch
               and lanes_left > 0):
            ctx_len = len(self._context(self.queue[0]))
            if ctx_len > self.max_seq - 1:
                self._reject(self.queue.popleft(), now)
                continue
            # reserve the first decode append too (an extra block exactly
            # when the context ends on a block boundary)
            need = self.pool.blocks_for(ctx_len + 1)
            if blocks_left < need:
                break
            batch.append(self.queue.popleft())
            self._claim(batch[-1])
            lanes_left -= 1
            blocks_left -= need
        if not batch:
            return
        groups: dict[int, list[Request]] = {}
        for req in batch:
            groups.setdefault(
                _bucket(len(self._context(req)), hi=self.max_seq),
                []).append(req)
        for s_bucket, reqs in sorted(groups.items()):
            self._prefill_bucket(s_bucket, reqs, now)

    def _prefill_bucket(self, s_bucket: int, reqs: list, now: float) -> None:
        """One batched prefill launch over the admitted requests of one
        length bucket (``engine.prefill``)."""
        with TraceAnnotation("engine.prefill") as span:
            with TraceAnnotation("engine.prefill.prepare"):
                rows = len(reqs)
                b_pad = _bucket(rows, lo=1)   # batch bucket bounds retraces
                tokens = np.zeros((b_pad, s_bucket), np.int32)
                lengths = np.ones(b_pad, np.int32)
                for i, req in enumerate(reqs):
                    ctx = self._context(req)
                    tokens[i, : len(ctx)] = ctx
                    lengths[i] = len(ctx)
                tokens_d = jnp.asarray(tokens)
                lengths_d = jnp.asarray(lengths)
            if TraceAnnotation.is_enabled():
                span.set_metadata(**self._prefill_args(
                    rows, int(lengths[:rows].sum()), rows))
            with TraceAnnotation("engine.prefill.launch"):
                logits, cache = self._prefill_jit(self.params, tokens_d,
                                                  lengths_d)
                d_cache = None
                if self._draft_prefill_jit is not None:
                    # drafter KV for the same rows, scattered into the SAME
                    # blocks (the draft pool shares this pool's block tables)
                    _, d_cache = self._draft_prefill_jit(
                        self._d_params, tokens_d, lengths_d)
            with TraceAnnotation("engine.prefill.sync"):
                nxt = self._next_from_prefill(logits, reqs, lengths)
            with TraceAnnotation("engine.prefill.commit"):
                self.stats.prefill_batches += 1
                self.stats.host_syncs += 1
                for i, req in enumerate(reqs):
                    lane = self.pool.insert(req.req_id, cache, i,
                                            int(lengths[i]))
                    if self._spec_on:
                        self.pool.set_hist(lane, self._context(req))
                    if d_cache is not None:
                        self.pool.insert_draft(req.req_id, d_cache, i,
                                               int(lengths[i]))
                    self.stats.prefill_tokens += int(lengths[i])
                    self._activate(req, int(nxt[i]), now)

    def _admit_chunked(self, now: float) -> None:
        """Chunked-prefill admission: claim a lane plus every block the
        context needs (reusing prefix-shared blocks), then let
        ``_prefill_tick`` stream the prompt into the pool one chunk per
        scheduler step, interleaved with decode."""
        while (self.queue and not self.knobs.paused
               and len(self.active) + len(self.prefilling)
               < self.knobs.max_batch):
            ctx = self._context(self.queue[0])
            if len(ctx) > self.max_seq - 1:
                self._reject(self.queue.popleft(), now)
                continue
            shared = self.pool.shared_prefix(ctx) if self.prefix_share \
                else []
            if self.prefix_share and self.pool.pending_shared(
                    ctx, have=len(shared)):
                # an in-flight prefill owns this prompt's next shareable
                # block: wait at the queue head and attach to its copy
                # instead of writing a duplicate (pending claims are
                # released on preemption, so the wait cannot deadlock)
                if self.queue[0].req_id != self._pending_waiter:
                    self._pending_waiter = self.queue[0].req_id
                    self.pool.pending_share_waits += 1
                break
            lane = self.pool.admit_prefill(self.queue[0].req_id, len(ctx),
                                           shared)
            if lane is None:
                break
            req = self.queue.popleft()
            self._claim(req, len(shared) * self.pool.block_size)
            if self._spec_on:
                self.pool.set_hist(lane, ctx)
            self.prefilling[req.req_id] = req
            self._prefill_pos[req.req_id] = \
                len(shared) * self.pool.block_size
            if self.prefix_share:
                self.pool.register_pending(req.req_id, ctx)
        return

    def _prefill_tick(self, now: float) -> int:
        """Advance every in-progress prefill by one chunk (a single jitted
        launch over all prefilling rows, padded to shared buckets)."""
        if not self.prefilling:
            return 0
        with TraceAnnotation("engine.prefill") as span:
            with TraceAnnotation("engine.prefill.prepare"):
                reqs = sorted(self.prefilling.values(),
                              key=lambda r: r.req_id)
                ctxs = [self._context(r) for r in reqs]
                takes = [min(self.prefill_chunk,
                             len(ctx) - self._prefill_pos[r.req_id])
                         for r, ctx in zip(reqs, ctxs)]
                c_pad = _bucket(max(takes), lo=min(16, self.prefill_chunk))
                b_pad = _bucket(len(reqs), lo=1)
                tokens = np.zeros((b_pad, c_pad), np.int32)
                starts = np.zeros(b_pad, np.int32)
                lens = np.zeros(b_pad, np.int32)
                tables = np.zeros((b_pad, self.pool.blocks_per_seq),
                                  np.int32)
                for i, (req, ctx, take) in enumerate(zip(reqs, ctxs, takes)):
                    p = self._prefill_pos[req.req_id]
                    tokens[i, :take] = ctx[p:p + take]
                    starts[i] = p
                    lens[i] = take
                    tables[i] = self.pool.block_tables[
                        self.pool.lane_of[req.req_id]]
                chunk = tuple(jnp.asarray(a)
                              for a in (tokens, starts, lens, tables))
                done_rows = [i for i, (req, ctx, take) in
                             enumerate(zip(reqs, ctxs, takes))
                             if self._prefill_pos[req.req_id] + take
                             == len(ctx)]
            if TraceAnnotation.is_enabled():
                span.set_metadata(**self._prefill_args(
                    len(reqs), sum(takes), len(done_rows)))
            with TraceAnnotation("engine.prefill.launch"):
                logits, self.pool.cache = self._prefill_chunk_jit(
                    self.params, self.pool.cache, *chunk)
                if self._draft_chunk_jit is not None:
                    # stream the same chunk through the drafter into its pool
                    _, self.pool.draft_cache = self._draft_chunk_jit(
                        self._d_params, self.pool.draft_cache, *chunk)
            nxt = None
            if done_rows:
                with TraceAnnotation("engine.prefill.sync"):
                    nxt = self._next_from_prefill(
                        logits, reqs,
                        np.asarray([self._prefill_pos[r.req_id] + t
                                    for r, t in zip(reqs, takes)]))
            with TraceAnnotation("engine.prefill.commit"):
                self.stats.prefill_batches += 1
                if done_rows:
                    self.stats.host_syncs += 1
                worked = 0
                for i, (req, ctx, take) in enumerate(zip(reqs, ctxs, takes)):
                    self._prefill_pos[req.req_id] += take
                    self.stats.prefill_tokens += take
                    worked += take
                    if self._prefill_pos[req.req_id] == len(ctx):
                        rid = req.req_id
                        del self.prefilling[rid]
                        del self._prefill_pos[rid]
                        self.pool.set_length(self.pool.lane_of[rid],
                                             len(ctx))
                        if self.prefix_share:
                            self.pool.register_prefix(rid, ctx)
                        self._activate(req, int(nxt[i]), now)
            return worked

    def _preempt(self, req_ids: list) -> None:
        """Pool ran dry: drop these requests' blocks and requeue them at the
        front for recompute (prompt + generated-so-far become the context)."""
        for rid in req_ids:
            req = self.active.pop(rid, None)
            if req is None:
                req = self.prefilling.pop(rid)
                del self._prefill_pos[rid]
            self.pool.release(rid)
            self._enqueue(req, front=True)
            self.stats.preemptions += 1

    def _lane_arrays(self, budgets: dict) -> tuple:
        """Per-lane decode inputs on the host: active mask, budgets, eos
        ids, temperatures, top-k and sampling seeds."""
        width = self.pool.n_lanes
        active_mask = np.zeros(width, bool)
        budget_arr = np.zeros(width, np.int32)
        eos_arr = np.full(width, -1, np.int32)
        temp_arr = np.zeros(width, np.float32)
        topk_arr = np.zeros(width, np.int32)
        seed_arr = np.zeros(width, np.int32)
        for rid, req in self.active.items():
            lane = self.pool.lane_of[rid]
            active_mask[lane] = True
            budget_arr[lane] = budgets[rid]
            if req.eos_id is not None:
                eos_arr[lane] = req.eos_id
            temp_arr[lane] = req.temperature
            topk_arr[lane] = req.top_k
            seed_arr[lane] = self._req_seed(req)
        return active_mask, budget_arr, eos_arr, temp_arr, topk_arr, seed_arr

    def _decode_paged(self, now: float) -> int:
        """Fused horizon decode: one jitted launch runs up to ``horizon``
        steps for every active lane; the host syncs once to drain the
        produced ``(tokens, emitted)`` horizon."""
        with TraceAnnotation("engine.decode") as span:
            with TraceAnnotation("engine.decode.prepare"):
                budgets = {rid: req.max_new_tokens - len(req.output)
                           for rid, req in self.active.items()}
                # always launch `horizon` steps: the scan skips drained tail
                # steps on-device (lax.cond), so num_steps stays one static
                # value and the decode graph never retraces mid-run
                n_eff = self.horizon
                # allocate append blocks oldest-request-first; when the pool
                # is exhausted the youngest actives are the ones preempted
                victims = self.pool.ensure_append_blocks(
                    sorted(self.active), horizon=n_eff, budgets=budgets)
                if victims:
                    self._preempt(victims)
                if TraceAnnotation.is_enabled():
                    span.set_metadata(**self._decode_args(n_eff,
                                                          len(victims)))
                if not self.active:
                    return 0
                mask, budget_arr, eos_arr, temps, top_ks, seeds = \
                    self._lane_arrays(budgets)
                # sampling arrays are only passed when some lane needs them,
                # so an all-greedy engine runs the identical pre-sampling
                # graph
                sampled = any(r.temperature > 0
                              for r in self.active.values())
                extra = dict(temps=jnp.asarray(temps),
                             top_ks=jnp.asarray(top_ks),
                             seeds=jnp.asarray(seeds)) if sampled else {}
                args = (self.pool.last_tokens_dev(), self.pool.positions(),
                        self.pool.tables(), jnp.asarray(mask),
                        jnp.asarray(budget_arr), jnp.asarray(eos_arr))
            with TraceAnnotation("engine.decode.launch") as launch:
                self._count_launch(launch)
                toks, emitted, _, (tok_f, pos_f, _, _), self.pool.cache = \
                    self._decode_multi_jit(
                        self.params, self.pool.cache, *args,
                        num_steps=n_eff, max_len=self.max_seq, **extra)
            with TraceAnnotation("engine.decode.sync"):
                toks_h = np.asarray(toks)    # the horizon's single host sync
                em_h = np.asarray(emitted)
            with TraceAnnotation("engine.decode.commit"):
                self.stats.host_syncs += 1
                self.stats.decode_syncs += 1
                # the loop's final device state becomes the pool mirror —
                # nothing is re-uploaded next launch; numpy mirrors updated
                # below
                self.pool.adopt_device("positions", pos_f)
                self.pool.adopt_device("last_tokens", tok_f)
                produced = 0
                finished = []
                for rid, req in list(self.active.items()):
                    lane = self.pool.lane_of[rid]
                    cnt = int(em_h[:, lane].sum())
                    req.output.extend(int(t) for t in toks_h[:cnt, lane])
                    produced += cnt
                    self.pool.lengths[lane] += cnt
                    self.pool.last_tokens[lane] = req.output[-1]
                    full = int(self.pool.lengths[lane]) + 1 > self.max_seq
                    if (len(req.output) >= req.max_new_tokens
                            or (req.eos_id is not None
                                and req.output[-1] == req.eos_id) or full):
                        finished.append(rid)
                for rid in finished:
                    self._finish(self.active.pop(rid), now, "accepted")
                    self.pool.release(rid)
                self.stats.decode_tokens += produced
            return produced

    def _decode_spec(self, now: float) -> int:
        """Fused speculative decode: each launch runs up to ``horizon``
        verify rounds; every round advances each lane by its accepted
        draft run + 1, so one host sync drains up to
        ``horizon * (spec_k + 1)`` tokens per lane."""
        with TraceAnnotation("engine.decode") as span:
            with TraceAnnotation("engine.decode.prepare"):
                budgets = {rid: req.max_new_tokens - len(req.output)
                           for rid, req in self.active.items()}
                k = self.spec_k
                # always launch `horizon` rounds: the scan skips exhausted
                # tail rounds on-device (lax.cond), so num_steps stays one
                # static value and the spec graph never retraces mid-run
                n_eff = self.horizon
                # each round may write KV up to spec_k slots past the
                # emitted run, so pad the per-request budgets by spec_k for
                # block reservation
                victims = self.pool.ensure_append_blocks(
                    sorted(self.active), horizon=n_eff * (k + 1),
                    budgets={rid: b + k for rid, b in budgets.items()})
                if victims:
                    self._preempt(victims)
                if TraceAnnotation.is_enabled():
                    span.set_metadata(**self._decode_args(n_eff,
                                                          len(victims)))
                if not self.active:
                    return 0
                lanes = tuple(jnp.asarray(a)
                              for a in self._lane_arrays(budgets))
                hist = self.pool.hist_dev()
                state = (self.pool.last_tokens_dev(), self.pool.positions(),
                         self.pool.tables())
            with TraceAnnotation("engine.decode.launch") as launch:
                self._count_launch(launch)
                toks, em, acc, (tok_f, pos_f, _, _), self.pool.cache, \
                    self.pool.draft_cache, hist_f = self._decode_spec_jit(
                        self.params, self.pool.cache, self._d_params,
                        self.pool.draft_cache, hist, *state, *lanes,
                        num_steps=n_eff, spec_k=k, max_len=self.max_seq,
                        ngram=self.ngram)
            with TraceAnnotation("engine.decode.sync"):
                toks_h = np.asarray(toks)     # (N, B, K+1) — the single sync
                em_h = np.asarray(em)
                acc_h = np.asarray(acc)       # (N, B) accepted drafts/round
            with TraceAnnotation("engine.decode.commit"):
                self.stats.host_syncs += 1
                self.stats.decode_syncs += 1
                self.pool.adopt_device("positions", pos_f)
                self.pool.adopt_device("last_tokens", tok_f)
                self.pool.adopt_device("hist", hist_f)
                produced = 0
                finished = []
                for rid, req in list(self.active.items()):
                    lane = self.pool.lane_of[rid]
                    em_l = em_h[:, lane, :]                      # (N, K+1)
                    # row-major boolean drain preserves round-then-slot
                    # order
                    new = [int(t) for t in toks_h[:, lane, :][em_l]]
                    cnt = len(new)
                    # a verify pass ran for this lane iff its slot 0 emitted
                    rounds = int(em_l[:, 0].sum())
                    self.stats.verify_passes += rounds
                    self.stats.draft_tokens += k * rounds
                    self.stats.accepted_tokens += int(
                        np.minimum(em_l.sum(axis=1), acc_h[:, lane]).sum())
                    if cnt:
                        base = int(self.pool.lengths[lane])
                        self.pool.token_hist[lane,
                                             base + 1: base + 1 + cnt] = new
                        req.output.extend(new)
                        produced += cnt
                        self.pool.lengths[lane] += cnt
                        self.pool.last_tokens[lane] = req.output[-1]
                    full = int(self.pool.lengths[lane]) + 1 > self.max_seq
                    if (len(req.output) >= req.max_new_tokens
                            or (req.eos_id is not None
                                and req.output[-1] == req.eos_id) or full):
                        finished.append(rid)
                for rid in finished:
                    self._finish(self.active.pop(rid), now, "accepted")
                    self.pool.release(rid)
                self.stats.decode_tokens += produced
            return produced

    def _decode_slots(self, now: float) -> int:
        with TraceAnnotation("engine.decode") as span:
            with TraceAnnotation("engine.decode.prepare"):
                if TraceAnnotation.is_enabled():
                    span.set_metadata(**self._decode_args(1, 0))
                lanes = {rid: self.pool.slot_of[rid] for rid in self.active}
                width = self.pool.n_slots
                tokens = [0] * width
                for rid, req in self.active.items():
                    tokens[lanes[rid]] = req.output[-1]
                tokens = jnp.asarray(tokens, jnp.int32)
                positions = self.pool.positions()
                sampled = any(r.temperature > 0
                              for r in self.active.values())
                if sampled:
                    temps = np.zeros(width, np.float32)
                    tks = np.zeros(width, np.int32)
                    seeds = np.zeros(width, np.int32)
                    for rid, req in self.active.items():
                        ln = lanes[rid]
                        temps[ln] = req.temperature
                        tks[ln] = req.top_k
                        seeds[ln] = self._req_seed(req)
                    t, tks, seeds = (jnp.asarray(temps), jnp.asarray(tks),
                                     jnp.asarray(seeds))
            with TraceAnnotation("engine.decode.launch") as launch:
                self._count_launch(launch)
                logits, self.pool.cache = self._decode_jit(
                    self.params, self.pool.cache, tokens, positions)
                v = self.model.cfg.vocab_size
                if sampled:
                    dist = sampling_dist(logits[:, :v], t, tks)
                    keys = event_keys(lane_keys(seeds), positions + 1,
                                      SALT_SAMPLE)
                    nxt = sample_from_dist(keys, dist, t <= 0.0)
                else:
                    nxt = jnp.argmax(logits[:, :v], axis=-1)
            with TraceAnnotation("engine.decode.sync"):
                nxt = np.asarray(nxt)
            with TraceAnnotation("engine.decode.commit"):
                self.stats.host_syncs += 1
                self.stats.decode_syncs += 1
                produced = 0
                finished = []
                for rid, req in list(self.active.items()):
                    ln = lanes[rid]
                    tok = int(nxt[ln])
                    req.output.append(tok)
                    produced += 1
                    full = int(self.pool.lengths[ln]) + 1 >= self.max_seq
                    if (len(req.output) >= req.max_new_tokens
                            or (req.eos_id is not None and tok == req.eos_id)
                            or full):
                        finished.append(rid)
                self.pool.advance(list(lanes.values()))
                for rid in finished:
                    self._finish(self.active.pop(rid), now, "accepted")
                    self.pool.release(rid)
                self.stats.decode_tokens += produced
            return produced

    def step(self, now: float | None = None) -> int:
        """One scheduler iteration: admit, advance chunked prefills, then
        run one decode launch (a fused ``horizon``-step loop in paged
        mode).  Returns number of decode tokens produced.
        """
        t0 = time.perf_counter()
        now = now if now is not None else t0
        if self.offline:
            return 0
        self._step_num += 1
        with StepTraceAnnotation("engine.step",
                                 step_num=self._step_num) as span:
            if TraceAnnotation.is_enabled():
                span.set_metadata(**self._step_args())
            if self._delayed or self._has_deadlines or self._guard_armed:
                with TraceAnnotation("engine.housekeeping"):
                    self._release_delayed(now)
                    self._expire_deadlines(now)
                    self._quarantine_scan(now)
            self._admit(now)
            prefilled = self._prefill_tick(now) \
                if self.paged and self.prefill_chunk else 0
            produced = 0
            if self.active:
                if self._spec_on:
                    produced = self._decode_spec(now)
                elif self.paged:
                    produced = self._decode_paged(now)
                else:
                    produced = self._decode_slots(now)
            if produced or prefilled:
                # simulated frequency knob: a capped clock stretches wall
                # time; a stuck-slow fault stretches it further
                self.stats.record_step((time.perf_counter() - t0)
                                       * self.slow_factor
                                       / max(self.knobs.freq_scale, 1e-3))
        return produced

    def run(self, *, max_steps: int = 10_000) -> EngineStats:
        steps = 0
        while (self.queue or self.active or self.prefilling
               or self._delayed) and steps < max_steps:
            self.step(now=float(steps))
            steps += 1
        return self.stats

    # -- goodput (paper §3.3) ----------------------------------------------
    def goodput(self, *, ttft_slo: float, tbt_slo: float) -> float:
        """Tokens/s over completed requests meeting both SLOs (times are in
        scheduler-step units when run() supplies logical `now`)."""
        return self.stats.goodput(ttft_slo=ttft_slo, tbt_slo=tbt_slo)
