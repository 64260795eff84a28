"""Continuous-batching scheduler over the paged K-Means KV cache.

Request lifecycle::

    QUEUED --admit (FCFS, free-block budget)--> RUNNING (prefilling)
    RUNNING --prompt fully written--> RUNNING (decoding)
    RUNNING --EOS / max-tokens--> FINISHED      (slot + blocks freed,
    RUNNING --pool exhausted--> PREEMPTED        refilled next step)
    PREEMPTED --requeued at the front--> QUEUED  (recompute on re-admission)

The hot loop is ONE jitted *packed* step of fixed shape: a grid of
``rows x seg_width`` token cells (``token_budget = rows * seg_width``). Every
scheduler iteration fills the grid with **segments** — each grid row is a
contiguous run of ONE request's tokens, carrying that request's slot id and
per-cell absolute positions (-1 = padded cell). Decoding requests get their
row reserved FIRST (admissions can never starve decode), prefill segments
fill the remaining rows FCFS (``seg_width`` tokens per row, so a chunk of
``n`` prompt tokens costs ``ceil(n / seg_width)`` block-table gathers instead
of ``n`` — the kernel attends a whole query segment per row). Each cell
writes its token's KV into the row's slot blocks and attends causally through
that slot's block table; rows and cells of the same request are causally
ordered by position within the same forward (write-then-attend).

**Speculative decoding** (``ServeConfig.speculative``): each decoding
request's reservation becomes a *verify segment* ``[next_token, d_1 .. d_k]``
— ``k`` greedy tokens proposed by a low-bit draft model
(``serving/speculative.py``: one scanned draft dispatch + private per-slot
paged pool) before the target step. The verify segment occupies
``ceil((k+1)/seg_width)`` consecutive grid rows — at the default
``seg_width=1`` that is k+1 flat rows, the SAME forward shape as
non-speculative serving, so per-row results are bit-identical and greedy
verification commits exactly the tokens plain greedy decoding would have
produced (the target's per-position argmaxes, applied via
``greedy_verify``). Rejected positions **roll back**:
their cache rows sit above the request's new context horizon (never attended,
rewritten by the next round's writes), and blocks holding only rejected
tokens are freed (``BlockAllocator.truncate`` — a shared tail block is only
decref'd). The draft's own state rewinds via a host-side counter.

**Prefix sharing** (``ServeConfig.prefix_cache``): as prefill fills a block
completely, the scheduler registers it with the allocator under the chain
hash of (pool identity, every token up to the block's end). Admission then
matches an incoming prompt's longest cached full-block prefix, increfs and
aliases those physical blocks into the new request's table, and sets
``prefilled`` past the shared tokens — their prefill compute is skipped
entirely; only the tail gets fresh blocks. Writes into a block whose
refcount exceeds 1 (aliased-last-block, or a verify segment reaching into a
shared block) are **copy-on-write**: the block's pool rows are copied
device-side into a fresh block and the table entry swapped before the packed
step, so ``attention_apply`` and the Pallas kernel never see sharing.
Deterministic K-Means assignment makes shared KV bit-identical to recomputed
KV, so sharing never changes sampled tokens.

Preemption is by eviction: when a decoding sequence cannot get a block, the
most recently admitted *other* request is evicted (blocks decref'd, requeued
front, prefill progress reset) and recomputed later — a decref only recycles
a block nobody else holds, and a re-admitted victim usually re-matches its
own just-registered prefix blocks, making recovery cheap. Cached refcount-0
prefix blocks are reclaimed (LRU) by the allocator before any preemption.

Sampling happens host-side from the logits the packed step returns (greedy
or per-request-keyed temperature): a decoding request samples from its
row's cells; a request whose LAST prompt token was written this step samples
its first token from that cell — per-request keys make sampled outputs
independent of how steps were packed. Speculative configs are greedy-only
(the rejection-sampling hook in speculative.py documents the temperature
contract and raises until implemented).
"""

from __future__ import annotations

import dataclasses
import enum
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from repro.serving.paged_cache import (
    BlockAllocator,
    PagedCacheConfig,
    blocks_needed,
    chain_hash,
    copy_blocks,
    prefix_seed,
    release_horizon,
    zero_state_slot,
)
from repro.serving.speculative import (
    DraftRunner,
    greedy_verify,
    make_packed_fn,
    make_probed_packed_fn,
    rejection_sample,
)
from repro.serving.telemetry import linear_buckets, log_buckets, make_telemetry

__all__ = ["RequestState", "Request", "Scheduler"]


def _softmax(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.float64) - x.max()
    e = np.exp(x)
    return e / e.sum()


class RequestState(enum.Enum):
    QUEUED = "queued"
    RUNNING = "running"
    PREEMPTED = "preempted"
    FINISHED = "finished"


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new_tokens: int
    eos_id: int | None
    key: jax.Array  # per-request sampling key (temperature > 0)
    state: RequestState = RequestState.QUEUED
    context: list[int] = dataclasses.field(default_factory=list)  # tokens fed
    generated: list[int] = dataclasses.field(default_factory=list)
    prefilled: int = 0  # context tokens written to the cache so far
    next_token: int | None = None  # sampled, not yet fed to the model
    blocks: list[int] = dataclasses.field(default_factory=list)
    block_hashes: list[bytes] = dataclasses.field(default_factory=list)  # chain
    slot: int = -1

    @property
    def decoding(self) -> bool:
        """Context fully written: the next packed step feeds next_token."""
        return self.prefilled >= len(self.context)

    @property
    def done(self) -> bool:
        if len(self.generated) >= self.max_new_tokens:
            return True
        return self.eos_id is not None and bool(self.generated) and \
            self.generated[-1] == self.eos_id

    def output(self) -> list[int]:
        """Exactly max_new_tokens tokens (eos-padded after early stop)."""
        out = list(self.generated[: self.max_new_tokens])
        pad = self.eos_id if self.eos_id is not None else 0
        return out + [pad] * (self.max_new_tokens - len(out))


@dataclasses.dataclass
class _StepPlan:
    """One packed step's plan: who runs, and where their tokens sit in the
    grid (``pos`` -1 = padded cell)."""

    decoders: list[Request]
    segments: list[tuple[Request, int, int]]  # (request, start, n) prefill runs
    drafts: dict[int, list[int]]  # rid -> proposed draft tokens
    draft_s: float  # seconds spent proposing them
    bt: np.ndarray  # (slots, max_blocks) block tables
    slot_ids: np.ndarray  # (rows,)
    pos: np.ndarray  # (rows, seg_width)
    tok: np.ndarray  # (rows, seg_width)
    verify_cells: dict[int, list]  # rid -> its decode / verify cells
    last_cell: dict[int, tuple[int, int]]  # rid -> its last prefill cell


class Scheduler:
    """Owns the block pool, the allocator, and the single jitted packed step.

    ``sc`` is a :class:`repro.serving.engine.ServeConfig`; its ``cache_len``
    bounds per-request context (prompt + generated), ``block_size`` /
    ``n_blocks`` size the pool (n_blocks=0 -> slots * blocks-per-request, a
    no-preemption default; pass a smaller pool to exercise preemption),
    ``token_budget`` sizes the packed grid (0 -> slots + prefill_chunk,
    rounded up to ``rows * seg_width`` cells with room for every slot's
    decode/verify segment), ``seg_width`` packs that many tokens per kernel
    segment row (default 1, the flat layout — under a speculative config a
    verify segment then spans k+1 flat rows, keeping forward shapes
    bit-identical to non-speculative serving), and ``prefix_cache`` enables
    refcounted prefix-block sharing.

    ``draft`` (speculative configs): ``(model, params)`` or
    ``(model, params, spec)`` — e.g. a ``load_quantized`` artifact tuple.

    Quality observability (``telemetry="quality"``): ``calib_stats`` is the
    per-tap calibration-time activation-stats dict persisted by
    ``core/artifact.save_quantized`` (``load_calib_stats``) — live probe
    stats drift-score against it (absent stats, the first probed step seeds
    a self-baseline); ``shadow_params`` is the reference parameter tree for
    the shadow quality probe (dense fp or a higher-precision spec) — None
    uses the serving params themselves (the self-referencing spec: KL ~ 0,
    agreement == 1 gates the probe machinery itself).
    """

    def __init__(self, model, params, sc, slots: int = 8, draft=None,
                 telemetry=None, calib_stats=None, shadow_params=None):
        policies = model.cache_policies()
        if policies is None:
            raise ValueError(
                f"family {model.cfg.family} exports no cache policies "
                "(cannot use the paged scheduler)"
            )
        self.policies = policies
        # per-policy resource model: paged layers cost blocks, recurrent
        # layers cost zero blocks but pin their slot's state; windowed layers
        # additionally allow freeing out-of-window blocks (release_horizon
        # is 0 whenever any full-attention layer still needs every block)
        self._has_paged = any(
            p.kind in ("paged_kv", "windowed_paged") for p in policies)
        self._rec = any(p.kind == "recurrent" for p in policies)
        self.release_window = release_horizon(policies)
        self.model, self.params, self.sc, self.slots = model, params, sc, slots
        self.telemetry = telemetry if telemetry is not None \
            else make_telemetry(getattr(sc, "telemetry", "metrics"))
        self.spec = sc.speculative
        self.draft: DraftRunner | None = None
        if self.spec is not None:
            if sc.temperature > 0:
                rejection_sample()  # greedy-only: raises NotImplementedError
            if draft is None:
                raise ValueError(
                    "speculative serving needs a draft model: pass "
                    "draft=(model, params[, spec]) or set "
                    "speculative.draft_artifact on the engine"
                )
            dm, dp, dspec = (tuple(draft) + (None,))[:3]
            if dm.cfg.vocab_size != model.cfg.vocab_size:
                raise ValueError(
                    f"draft vocab {dm.cfg.vocab_size} != target vocab "
                    f"{model.cfg.vocab_size}: verification compares argmaxes"
                )
            self.draft = DraftRunner(
                dm, dp, slots=slots, cache_len=sc.cache_len, k=self.spec.k,
                block_size=sc.block_size,
                cache_dtype=jnp.dtype(dspec.kv_dtype if dspec else sc.cache_dtype),
                kv_quant=(dspec.kv_bits is not None) if dspec else sc.kv_quant,
                token_budget=self.spec.draft_token_budget,
                telemetry=self.telemetry,
            )
        # grid geometry: rows x seg_width cells. Decode reservation needs
        # every slot's verify segment (k+1 tokens under speculation, 1
        # otherwise) to fit simultaneously. seg_width only changes how many
        # cells share one kernel segment row (one block-table gather per
        # row); it never changes which tokens run — a seg_width=1 grid with
        # the same cell budget is bit-identical, which is what keeps
        # speculative greedy exactly equal to non-speculative greedy.
        self.seg_width = max(1, sc.seg_width)
        seg_len = (self.spec.k + 1) if self.spec else 1
        self._dec_rows = -(-seg_len // self.seg_width)  # rows per decode seg
        base = sc.token_budget or (slots + sc.prefill_chunk)
        rows = -(-base // self.seg_width)
        if sc.token_budget == 0:
            rows = max(rows, slots * self._dec_rows)
        if rows < slots * self._dec_rows:
            raise ValueError(
                f"token_budget {base} gives {rows} segment rows of width "
                f"{self.seg_width} but decode reservation needs "
                f"{slots * self._dec_rows} (slots x ceil((k+1)/seg_width))"
            )
        if self._rec and self._dec_rows > 1:
            raise ValueError(
                "recurrent layers gather/scatter state by slot, so a verify "
                f"segment must fit in ONE grid row: raise seg_width to >= "
                f"k+1 = {seg_len} (got {self.seg_width})"
            )
        self.rows = rows
        self.token_budget = rows * self.seg_width
        max_blk = blocks_needed(sc.cache_len, sc.block_size)
        n_blocks = sc.n_blocks or slots * max_blk
        self.pcfg = PagedCacheConfig(block_size=sc.block_size, n_blocks=n_blocks,
                                     max_blocks_per_seq=max_blk)
        self.pools = model.init_caches(
            slots, sc.cache_len, jnp.dtype(sc.cache_dtype), quantized=sc.kv_quant,
            layout="paged", block_size=sc.block_size, n_blocks=n_blocks,
        )
        # prefix sharing aliases physical blocks across requests, which only
        # composes with layers that keep every block forever: windowed layers
        # free out-of-window blocks (an alias would free a shared block) and
        # recurrent layers have no blocks to share
        prefix_on = sc.prefix_cache and bool(policies) and \
            all(p.kind == "paged_kv" for p in policies)
        self.allocator = BlockAllocator(n_blocks, prefix_cache=prefix_on,
                                        telemetry=self.telemetry)
        # chain-hash root: blocks are only shareable within one (layer-set,
        # quant-policy, geometry) identity — a pool restarted with a different
        # KV treatment can never alias stale hashes
        self._hash_seed = prefix_seed(
            family=model.cfg.family, n_layers=model.cfg.n_layers,
            n_kv_heads=model.cfg.n_kv_heads, head_dim=model.cfg.head_dim,
            kv_quant=sc.kv_quant, cache_dtype=str(sc.cache_dtype),
            block_size=sc.block_size,
        )
        self._queue: deque[Request] = deque()
        self._running: list[Request] = []
        self._slot_free = list(range(slots - 1, -1, -1))
        self._next_rid = 0
        # serving counters live in the telemetry registry (Scheduler.stats
        # rebuilds the legacy dict from them); cached as attributes so the
        # hot loop pays one method call, and all of them no-op at level=off
        tel = self.telemetry
        self._c = {k: tel.counter(f"serving_{k}") for k in (
            "packed_steps", "decode_steps", "prefill_chunks", "mixed_steps",
            "decode_slot_tokens", "prefill_tokens", "packed_tokens",
            "prefix_hits", "prefix_hit_tokens", "prefill_skipped",
            "cow_copies", "spec_rounds", "drafted_tokens", "accepted_tokens",
            "rolled_back_tokens")}
        self._c["preemptions"] = tel.counter("serving_preemptions")
        self._g_peak = tel.gauge("serving_pool_occupancy_peak",
                                 "high-water live-block fraction")
        tel.gauge("serving_queue_depth", fn=lambda: len(self._queue))
        tel.gauge("serving_running_requests", fn=lambda: len(self._running))
        # LUT-GEMM route dispatch (core/kernel_routing): trace-time counts of
        # which GEMM path each projection compiled into — pallas fused kernel
        # vs jnp factorized vs explicit fallback. Lazy gauges over the
        # process-global registry, so "which GEMM path actually ran" is
        # answerable from any telemetry snapshot.
        from repro.core import kernel_routing as _kr

        self._g_lut = {
            "lut_kernel_calls": tel.gauge(
                "serving_lut_kernel_calls", fn=_kr.kernel_calls,
                help="projections routed to the fused Pallas LUT-GEMM"),
            "lut_jnp_calls": tel.gauge(
                "serving_lut_jnp_calls", fn=_kr.jnp_calls,
                help="projections routed to the jnp factorized LUT-GEMM"),
            "lut_fallbacks": tel.gauge(
                "serving_lut_fallbacks", fn=_kr.fallback_count,
                help="explicit pallas->jnp tier fallbacks"),
        }
        # Orizuru outlier-engine dispatch: which detection path each dual-
        # branch projection compiled into, plus the compensation route
        # (gather, scatter, or fused into the LUT-GEMM kernel) its comp_mode
        # resolved to. Same lazy-gauge
        # pattern as the LUT-GEMM counters above.
        self._g_outlier = {
            "outlier_detect_calls": tel.gauge(
                "serving_outlier_detect_calls", fn=_kr.detect_calls,
                help="outlier-branch detection resolutions (any route)"),
            "outlier_kernel_calls": tel.gauge(
                "serving_outlier_kernel_calls", fn=_kr.detect_kernel_calls,
                help="detections routed to the Pallas Orizuru kernel"),
            "outlier_jnp_calls": tel.gauge(
                "serving_outlier_jnp_calls", fn=_kr.detect_jnp_calls,
                help="detections routed to lax.top_k / threshold scoring"),
            "outlier_fallbacks": tel.gauge(
                "serving_outlier_fallbacks", fn=_kr.detect_fallback_count,
                help="explicit detection pallas->jnp demotions"),
            "outlier_comp_gather": tel.gauge(
                "serving_outlier_comp_gather",
                fn=lambda: _kr.comp_route_counts().get("gather", 0),
                help="compensations resolved to the row-gather route"),
            "outlier_comp_scatter": tel.gauge(
                "serving_outlier_comp_scatter",
                fn=lambda: _kr.comp_route_counts().get("scatter", 0),
                help="compensations resolved to the scatter+dense route"),
            "outlier_comp_fused": tel.gauge(
                "serving_outlier_comp_fused",
                fn=lambda: _kr.comp_route_counts().get("fused", 0),
                help="compensations folded into the fused LUT-GEMM kernel"),
        }
        self._h_accept = tel.histogram(
            "serving_spec_accepted_per_round",
            linear_buckets(0.0, float(self.spec.k + 1) if self.spec else 1.0,
                           (self.spec.k + 1) if self.spec else 1),
            "accepted draft tokens per verify round")
        self._h_draft_round = tel.histogram(
            "serving_draft_round_s", log_buckets(1e-6, 1e2),
            "draft propose (catch-up + scan) per round, seconds")
        self._c_draft_time = tel.counter(
            "serving_draft_time_s", "total seconds in draft proposal")
        self._c_target_time = tel.counter(
            "serving_target_time_s", "total seconds in target packed steps")
        self._g_live_peak = tel.gauge(
            "serving_peak_live_blocks_per_seq",
            help="high-water LIVE (non-freed) blocks held by any one request "
                 "— bounded by ceil(window/block_size)+1 under windowed_paged")
        self._packed_fn = jax.jit(make_packed_fn(model))
        # profiles of the packed step are read by its named scopes
        # (telemetry.scope_map), which live in the executable's metadata;
        # JAX's persistent compile cache leaves metadata out of its key by
        # default, and would hand back an executable named by whichever
        # revision of the program compiled it first
        jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
        self._copy_fn = jax.jit(copy_blocks)
        if self._rec:
            self._zero_fn = jax.jit(zero_state_slot)
            self._commit_fn = jax.jit(self._make_commit_fn())
        # ---- quality level: quantization-numerics observability ----------
        # Every other level keeps self._packed_fn untouched (its jaxpr is
        # asserted identical to a probe-free build); quality swaps in the
        # PROBED packed step on sampled steps and pays its recompile.
        self._quality = None
        self._probe_fn = None
        self._step_i = 0
        if getattr(tel, "quality", False):
            from repro.core import numerics as _nx

            self._probe_fn = jax.jit(make_probed_packed_fn(model))
            self._quality = _nx.QualityMonitor(
                tel, calib_stats=calib_stats,
                drift_threshold=tel.cfg.quality_drift_threshold)
            self._shadow_params = (shadow_params if shadow_params is not None
                                   else params)
            self._shadow_len = sc.cache_len
            self._shadow_fn = jax.jit(self._make_shadow_fn())
            self._h_shadow_kl = tel.histogram(
                "numerics_shadow_logit_kl", log_buckets(1e-9, 1e3),
                "KL(serving || shadow reference) at the probed decode "
                "position, nats")
            self._g_shadow_top1 = tel.gauge(
                "numerics_shadow_top1_agreement",
                "serving vs shadow argmax agreement at the probed position")
            self._g_shadow_agree = tel.gauge(
                "numerics_shadow_token_agreement",
                "teacher-forced shadow greedy agreement over the committed "
                "decode window")
            self._c_shadow = tel.counter(
                "numerics_shadow_probes", "shadow-reference forwards run")
            if self.spec is not None:
                self._h_first_reject = tel.histogram(
                    "numerics_spec_first_reject_pos",
                    linear_buckets(0.0, float(self.spec.k + 1),
                                   self.spec.k + 1),
                    "draft position of the first greedy rejection "
                    "(acceptance attribution; full accepts not observed)")

    @property
    def stats(self) -> dict:
        """Legacy counter dict, rebuilt from the telemetry registry (all
        zeros under ``telemetry="off"``). Read-only: mutate via telemetry."""
        d = {k: c.value for k, c in self._c.items()}
        d["peak_occupancy"] = self._g_peak.value
        d["peak_live_blocks_per_seq"] = self._g_live_peak.value
        for k, g in self._g_lut.items():  # trace-time LUT route dispatch
            d[k] = g.value
        for k, g in self._g_outlier.items():  # Orizuru detect + comp routes
            d[k] = g.value
        return d

    # ----------------------------------------------------------------- host
    def submit(self, prompt: list[int], max_new_tokens: int,
               eos_id: int | None = None, seed: int = 0,
               salt: int | None = None) -> int:
        """``salt`` individualizes the sampling key within one batch of
        submissions (the engine passes the request's index) so a given
        (seed, request set) resamples identically across generate calls."""
        if not prompt:
            raise ValueError("empty prompt (nothing to prefill)")
        if len(prompt) + max_new_tokens > self.pcfg.max_context:
            raise ValueError(
                f"prompt({len(prompt)}) + max_new({max_new_tokens}) exceeds "
                f"cache_len {self.pcfg.max_context}"
            )
        rid = self._next_rid
        self._next_rid += 1
        r = Request(rid=rid, prompt=list(prompt), max_new_tokens=max_new_tokens,
                    eos_id=eos_id,
                    key=jax.random.PRNGKey(seed * 100_003 + (rid if salt is None else salt)),
                    context=list(prompt))
        self._queue.append(r)
        self.telemetry.request_submitted(rid, len(prompt))
        return rid

    def run(self) -> dict[int, list[int]]:
        """Drain queue + running set; returns {rid: generated tokens}."""
        results: dict[int, list[int]] = {}
        while self.step(results):
            pass
        return results

    def step(self, results: dict[int, list[int]]) -> bool:
        """One scheduler iteration: refill slots from the queue, retire
        finished requests, run one packed token-budget forward over all
        running slots. Finished outputs are added to ``results``. Returns
        True while work remains — online drivers (bench_serving) interleave
        ``submit`` between steps.
        """
        with self.telemetry.annotate("sched.admit", step=self._step_i):
            admitted = self._refill_slots()
            for r in [r for r in self._running if r.done]:
                self._finish(r, results)
        if self._running:
            self._packed_once(results)
            return True
        if self._queue and not admitted:  # head can never fit: pool all idle
            r = self._queue[0]
            need = self._blocks_for(len(r.context) + 1)
            raise RuntimeError(
                f"request {r.rid} needs {need} blocks (context + first decode);"
                f" pool has {self.allocator.n_free}/{self.pcfg.n_blocks} free"
            )
        return bool(self._queue)

    # ------------------------------------------------------------- admission
    def _refill_slots(self) -> int:
        """FCFS admission: head of queue enters iff a slot is free and the
        pool can hold its full current context PLUS the first decode token
        (reserving ``blocks_needed(len + 1)`` up front — admitting on an
        exact fit used to let a block_size-multiple prompt be preempted by
        its own first ``_grow``). Returns #admitted. Admission only binds a
        slot + blocks; the prompt is written by the packed steps (alongside
        everyone else's decode tokens), never serially.

        With the prefix cache on, the longest chain of cached full blocks is
        aliased (incref) instead of allocated, and ``prefilled`` starts past
        the shared tokens — capped at ``len(context) - 1`` so at least one
        prompt token is always computed (its logits seed sampling). The draft
        runner never shares that skip: its slot state resets to 0 and the
        whole prompt replays through the draft on the first proposal."""
        admitted = 0
        bs = self.pcfg.block_size
        while self._queue and self._slot_free:
            r = self._queue[0]
            need = self._blocks_for(len(r.context) + 1)
            shared, hashes = self._match_prefix(r)  # increfs on hit
            fresh = self.allocator.alloc(need - len(shared))
            if fresh is None:
                if shared:  # roll the aliases back: blocks return to cached
                    self.allocator.free(list(reversed(shared)))
                break
            self._queue.popleft()
            r.blocks, r.block_hashes = shared + fresh, hashes
            r.slot, r.state = self._slot_free.pop(), RequestState.RUNNING
            r.prefilled = min(len(shared) * bs, len(r.context) - 1)
            if self._rec:
                # fresh occupant: the slot's recurrent state must not leak
                # from the previous request (KV blocks are freshly allocated,
                # state slots are reused in place)
                self.pools = self._zero_fn(self.pools, r.slot)
            if self.draft is not None:
                self.draft.reset(r.slot)
            if shared:
                self._c["prefix_hits"].add()
                self._c["prefix_hit_tokens"].add(len(shared) * bs)
                self._c["prefill_skipped"].add(r.prefilled)
            self.telemetry.request_admitted(r.rid,
                                            prefix_hit_tokens=len(shared) * bs)
            self._running.append(r)
            admitted += 1
        self._g_peak.set_max(self.allocator.occupancy)
        return admitted

    def _match_prefix(self, r: Request) -> tuple[list[int], list[bytes]]:
        """Longest cached full-block prefix of r.context: walks the chain
        hash block by block, increfs every hit (reviving cached refcount-0
        blocks), stops at the first miss. Returns (block ids, chain hashes)."""
        if not self.allocator.prefix_cache:
            return [], []
        bs = self.pcfg.block_size
        ids: list[int] = []
        hashes: list[bytes] = []
        h = self._hash_seed
        for j in range(len(r.context) // bs):
            h = chain_hash(h, r.context[j * bs : (j + 1) * bs])
            bid = self.allocator.lookup(h)
            if bid is None:
                break
            self.allocator.incref(bid)
            ids.append(bid)
            hashes.append(h)
        return ids, hashes

    # ------------------------------------------------------------ packed step
    def _k_for(self, r: Request) -> int:
        """Draft tokens to propose for ``r`` this round: the configured k,
        clipped so verification can never commit past the request's remaining
        generation budget (which also bounds every write below cache_len —
        ``submit`` checked prompt + max_new against the pool geometry)."""
        if self.spec is None:
            return 0
        return max(0, min(self.spec.k,
                          r.max_new_tokens - len(r.generated) - 1))

    def _packed_once(self, results: dict) -> None:
        """Assemble and run one token-budget grid forward.

        Budget policy: decode/verify segments FIRST (``ceil((k+1)/seg_width)``
        rows per decoding slot — a step can never stall decode to admit),
        then prefill segments FCFS over the remaining rows (a request's next
        unwritten context tokens, packed ``seg_width`` per row, clipped to
        the rows that fit; large prompts span several steps).

        Telemetry spans, each with ``step=<packed-step number>``, under one
        ``packed_step`` span: ``sched.plan`` (reservation, copy-on-write,
        grid fill), ``sched.dispatch`` (transfers + the jitted call),
        ``sched.wait`` (blocks on the logits: the device's step),
        ``sched.sample`` (reading and sampling the logits) and
        ``sched.commit`` (bookkeeping, finishing, probes, the step record).
        """
        tel = self.telemetry
        step = self._step_i
        with tel.annotate("packed_step", step=step):
            t_host0 = tel.now()
            blocks_alloc0 = self.allocator.blocks_allocated
            blocks_freed0 = self.allocator.blocks_freed
            cow0 = self._c["cow_copies"].value
            with tel.annotate("sched.plan", step=step):
                plan = self._plan()
                if plan is None:
                    return
                # per-row horizon (all-pad rows stay 0)
                ctx = plan.pos.max(axis=1) + 1
            # quality level: 1 in quality_sample_every steps runs the PROBED
            # packed fn (step 0 included, so short smokes populate every
            # gauge); all other steps — and every other level — dispatch the
            # untouched packed step
            probe_now = (self._probe_fn is not None and
                         step % tel.cfg.quality_sample_every == 0)
            probes = None
            with tel.annotate("sched.dispatch", step=step):
                t_dispatch = tel.now()
                args = (self.params, self.pools, jnp.asarray(plan.bt),
                        jnp.asarray(plan.slot_ids), jnp.asarray(plan.pos),
                        jnp.asarray(ctx), jnp.asarray(plan.tok))
                if probe_now:
                    self.pools, logits, extras, probes = self._probe_fn(*args)
                else:
                    self.pools, logits, extras = self._packed_fn(*args)
            with tel.annotate("sched.wait", step=step):
                # times the device's step apart from the host's work; sampling
                # blocks on the logits anyway, so this adds no dispatch (only
                # the first sample's small dispatches no longer queue behind
                # the step)
                jax.block_until_ready(logits)
            t_done = tel.now()
            self._c_target_time.add(t_done - t_dispatch)
            with tel.annotate("sched.sample", step=step):
                picks, am, shadow_args = self._sample_step(plan, logits)
            with tel.annotate("sched.commit", step=step):
                n_cells = self._commit(plan, results, picks, am, extras, probes,
                                       shadow_args)
                if tel.enabled:
                    dec_rows = len(plan.decoders) * self._dec_rows
                    tel.step_record(
                        host_s=(t_dispatch - t_host0 - plan.draft_s) + (tel.now() - t_done),
                        device_s=t_done - t_dispatch,
                        cells=n_cells, budget=self.token_budget,
                        decode_rows=0 if self.spec else dec_rows,
                        verify_rows=dec_rows if self.spec else 0,
                        prefill_rows=sum(-(-n // self.seg_width)
                                         for _, _, n in plan.segments),
                        blocks_allocated=self.allocator.blocks_allocated - blocks_alloc0,
                        blocks_freed=self.allocator.blocks_freed - blocks_freed0,
                        blocks_copied=self._c["cow_copies"].value - cow0,
                    )

    def _plan(self) -> _StepPlan | None:
        """Reserve blocks (growth, copy-on-write; may preempt), propose
        drafts, and fill the grid. None when preemption left nothing
        running."""
        S = self.seg_width
        tel = self.telemetry
        while True:
            # decode reservation: guarantee blocks for every incoming token
            # (may preempt — victims leave self._running, incl. prefilling)
            for r in list(self._running):
                if r.state is RequestState.RUNNING and r.decoding:
                    self._grow(r, self._k_for(r) + 1)
            if not self._running:
                return None
            decoders = [r for r in self._running if r.decoding]
            segments: list[tuple[Request, int, int]] = []  # (request, start, n)
            rows_left = self.rows - len(decoders) * self._dec_rows
            for r in self._running:
                if rows_left <= 0:
                    break
                if not r.decoding:
                    # recurrent state is gathered/scattered once per row, so
                    # a slot gets at most ONE row per step: cap its prefill
                    # chunk at seg_width tokens (pure-KV stacks may span rows)
                    cap = S if self._rec else rows_left * S
                    n = min(cap, len(r.context) - r.prefilled)
                    segments.append((r, r.prefilled, n))
                    rows_left -= -(-n // S)
            if not self._has_paged:
                break  # no blocks -> nothing to copy-on-write
            if self._cow_pass(decoders, segments):
                break  # no preemption mid-pass: the plan above is still live

        # draft proposal AFTER the plan is stable (growth/COW preemptions are
        # done, so no proposal is wasted on an evicted request); the draft
        # pool is private, so proposing cannot invalidate the plan
        drafts: dict[int, list[int]] = {}
        draft_dt = 0.0
        if self.draft is not None and decoders:
            t_d0 = tel.now()
            drafts = self.draft.propose(
                [(r.rid, r.slot, r.context, r.next_token, self._k_for(r))
                 for r in decoders])
            draft_dt = tel.now() - t_d0
            self._h_draft_round.observe(draft_dt)
            self._c_draft_time.add(draft_dt)

        max_blk = self.pcfg.max_blocks_per_seq
        bt = np.full((self.slots, max_blk), -1, np.int32)
        slot_ids = np.zeros((self.rows,), np.int32)
        pos = np.full((self.rows, S), -1, np.int32)
        tok = np.zeros((self.rows, S), np.int32)
        for r in self._running:
            bt[r.slot] = self._bt_row(r)
        row = 0

        def fill(seq, start_pos, slot):
            """Pack one request's token run into consecutive grid cells
            starting on a fresh row; returns the cell coordinates."""
            nonlocal row
            cells = []
            for j, t in enumerate(seq):
                rr, cc = row + j // S, j % S
                slot_ids[rr] = slot
                pos[rr, cc] = start_pos + j
                tok[rr, cc] = t
                cells.append((rr, cc))
            row += -(-len(seq) // S)
            return cells

        # decode/verify segments first (the reservation above sized them in),
        # then prefill segments over the remaining rows
        verify_cells: dict[int, list] = {}
        for r in decoders:
            verify_cells[r.rid] = fill([r.next_token] + drafts.get(r.rid, []),
                                       len(r.context), r.slot)
        last_cell: dict[int, tuple[int, int]] = {}
        for r, start, n in segments:
            last_cell[r.rid] = fill(r.context[start : start + n], start,
                                    r.slot)[-1]
        return _StepPlan(decoders, segments, drafts, draft_dt, bt, slot_ids, pos,
                         tok, verify_cells, last_cell)

    def _sample_step(self, plan: _StepPlan, logits):
        """Read this step's tokens from its logits: each plain decoder's
        next token, the first token of each request whose prompt finished
        this step, every verify argmax under speculation, and the quality
        level's shadow-probe logits. Returns (picks by rid, verify argmaxes
        or None, shadow-probe args or None)."""
        decoders, verify_cells = plan.decoders, plan.verify_cells
        picks: dict[int, int] = {}
        am = None
        if self.spec is not None and decoders:
            # one device->host transfer of every verify argmax
            am = np.asarray(jnp.argmax(logits, axis=-1))
        shadow_args = None
        if (self._quality is not None and decoders and
                self._step_i % self.telemetry.cfg.quality_shadow_every == 0):
            # deepest committed context = most decode positions to audit;
            # the first verify cell's logits condition on context +
            # [next_token] — the prefix the shadow forward replays
            r = max(decoders, key=lambda q: len(q.context))
            rw0, cc0 = verify_cells[r.rid][0]
            shadow_args = (r, np.asarray(logits[rw0, cc0], np.float32),
                           len(r.context) + 1)
        if self.spec is None:
            for r in decoders:
                rw, cc = verify_cells[r.rid][0]
                picks[r.rid] = self._sample(logits[rw, cc], r)
        for r, start, n in plan.segments:
            if start + n >= len(r.context) and r.next_token is None:
                # the prompt's real last token was in this step: its logits
                # cell is the first sampled token (a re-admitted preemption
                # keeps its already-decided next_token instead)
                rw, col = plan.last_cell[r.rid]
                picks[r.rid] = self._sample(logits[rw, col], r)
        return picks, am, shadow_args

    def _commit(self, plan: _StepPlan, results, picks, am, extras, probes,
                shadow_args) -> int:
        """Apply one step's sampled tokens: decode / verify bookkeeping,
        prefill progress and first tokens, prefix-block registration, window
        release, finishing, and the quality probes. Returns the step's
        valid cells."""
        tel = self.telemetry
        decoders, segments = plan.decoders, plan.segments
        n_cells = int((plan.pos >= 0).sum())
        st = self._c
        st["packed_steps"].add()
        st["packed_tokens"].add(n_cells)
        st["prefill_tokens"].add(sum(n for _, _, n in segments))
        st["prefill_chunks"].add(len(segments))
        if decoders:
            st["decode_steps"].add()
        if decoders and segments:
            st["mixed_steps"].add()
        for r in decoders:
            cells = plan.verify_cells[r.rid]
            r.context.append(r.next_token)
            r.prefilled += 1  # the segment's first cell wrote it to the cache
            if self.spec is None:
                r.next_token = picks[r.rid]
                r.generated.append(r.next_token)
                st["decode_slot_tokens"].add()
                tel.tokens_committed(r.rid, 1)
                continue
            d = plan.drafts.get(r.rid, [])
            committed = greedy_verify([int(am[rr, cc]) for rr, cc in cells], d,
                                      r.eos_id)
            # all committed-but-last tokens have valid KV already in the
            # cache (their cells matched the drafts written this step); the
            # last one is the new pending next_token
            r.context.extend(committed[:-1])
            r.prefilled += len(committed) - 1
            r.next_token = committed[-1]
            r.generated.extend(committed)
            # acceptance accounting: every committed-but-last token matched
            # its draft by construction; the last counts too when it is an
            # EOS that agreed with its draft (committed, just absorbing)
            n_acc = len(committed) - 1
            if n_acc < len(d) and committed[-1] == d[n_acc]:
                n_acc += 1
            st["spec_rounds"].add()
            st["drafted_tokens"].add(len(d))
            st["accepted_tokens"].add(n_acc)
            st["rolled_back_tokens"].add(len(d) - n_acc)
            st["decode_slot_tokens"].add(len(committed))
            self._h_accept.observe(n_acc)
            if self._quality is not None and n_acc < len(d):
                # acceptance attribution: which draft position broke first
                self._h_first_reject.observe(float(n_acc))
            tel.tokens_committed(r.rid, len(committed))
            tel.request_event(r.rid, "verify_round", drafted=len(d),
                              accepted=n_acc, committed=len(committed))
            self._rollback(r)
            if self._rec and len(committed) < len(cells):
                # the packed step scattered recurrent state at the row's last
                # cell; rewind it to the last CONSUMED cell (next_token +
                # committed[:-1] = cells 0..len(committed)-1)
                self.pools = self._commit_fn(
                    self.pools, extras, cells[0][0], r.slot,
                    len(committed) - 1)
            self.draft.sync(r.slot, len(r.context))
        for r, start, n in segments:
            r.prefilled = start + n
            tel.request_event(r.rid, "prefill_chunk", start=start, n=n)
            if r.rid in picks:
                r.next_token = picks[r.rid]
                r.generated.append(r.next_token)
                tel.first_token(r.rid)
        for r in self._running:
            self._register_full_blocks(r)  # publish before anyone finishes
        if self._has_paged:
            for r in self._running:
                if self.release_window:
                    self._release_windowed(r)
                self._g_live_peak.set_max(
                    sum(1 for b in r.blocks if b >= 0))
        for r in [r for r in self._running if r.done]:
            self._finish(r, results)
        if probes is not None:
            # one transfer of the whole probe dict -> gauges + drift/alarms
            self._quality.ingest(jax.device_get(probes))
        if shadow_args is not None:
            self._shadow_probe(*shadow_args)
        self._step_i += 1
        return n_cells

    def _blocks_for(self, n_tokens: int) -> int:
        """Blocks to reserve for an ``n_tokens`` context: zero when no layer
        is paged — recurrent state is slot-major, pinned by the slot."""
        if not self._has_paged:
            return 0
        return blocks_needed(n_tokens, self.pcfg.block_size)

    def _make_shadow_fn(self):
        """Jitted shadow-reference forward: a plain cache-free teacher-forced
        run over one request's committed context, zero-padded to a fixed
        length (``cache_len``) so it compiles once. Causality makes the
        padding inert for every position actually read."""
        model = self.model

        def shadow(params, tokens):  # (1, L) int32
            out = model.apply(params, {"tokens": tokens})
            return out.logits[0, :, : model.cfg.vocab_size]

        return shadow

    def _shadow_probe(self, r: Request, served: np.ndarray,
                      prefix_len: int) -> None:
        """Off-hot-path shadow quality probe (quality level, sampled): re-run
        ``r``'s committed context through the reference forward; record the
        logit KL + top-1 agreement at the probed decode position (vs the
        serving step's own logits for the same prefix) and the teacher-forced
        greedy-token agreement over the whole committed decode window."""
        toks = r.context[: self._shadow_len]
        m = len(toks)
        if m < 2:
            return
        padded = np.zeros((1, self._shadow_len), np.int32)
        padded[0, :m] = toks
        sl = np.asarray(jax.device_get(
            self._shadow_fn(self._shadow_params, jnp.asarray(padded))),
            np.float32)[:m]
        i = prefix_len - 1
        if 0 <= i < m:
            p, q = _softmax(served), _softmax(sl[i])
            kl = max(float(np.sum(p * (np.log(p + 1e-12)
                                       - np.log(q + 1e-12)))), 0.0)
            self._h_shadow_kl.observe(kl)
            self._g_shadow_top1.set(
                float(int(np.argmax(served)) == int(np.argmax(sl[i]))))
            self.telemetry.quality_counter("numerics_shadow_logit_kl", kl)
        start = max(len(r.prompt) - 1, 0)
        if m - 1 > start:
            pred = np.argmax(sl[start: m - 1], axis=-1)
            ref = np.asarray(toks[start + 1: m])
            self._g_shadow_agree.set(float((pred == ref).mean()))
        self._c_shadow.add()

    def _make_commit_fn(self):
        """Jitted corrective commit for recurrent layers: a verify row's
        packed step scattered the state at the row's LAST cell, but greedy
        verification may consume only cells 0..m-1 — rewrite each state pool
        from the per-cell "*_steps" transients at the last consumed cell.
        Generic over the extras keys, so fp ("h_steps"/"conv_steps") and
        quantized ("h_idx_steps"/"h_scale_steps"/"conv_steps") layers both
        rewind; KV layers have empty extras and pass through."""

        def fix_layer(pool, extras, row, slot, step, scanned):
            out = dict(pool)
            for key, steps in extras.items():
                base = key[: -len("_steps")]
                if scanned:  # leading (L, ...) layer dim rides the arrays
                    out[base] = out[base].at[:, slot].set(steps[:, row, step])
                else:
                    out[base] = out[base].at[slot].set(steps[row, step])
            return out

        def commit(pools, extras, row, slot, step):
            if isinstance(pools, dict):  # scan-stacked homogeneous family
                return fix_layer(pools, extras, row, slot, step, True)
            return [fix_layer(lp, le, row, slot, step, False)
                    for lp, le in zip(pools, extras)]

        return commit

    def _rollback(self, r: Request) -> None:
        """Free the blocks a verify segment grew that now hold only rejected
        draft tokens: everything past ``blocks_needed(len(context) + 1)``
        (context plus the pending next_token write — the admission-time
        reservation invariant). Rejected writes *inside* a kept block need no
        cleanup: they sit above the context horizon, are masked out of every
        read, and are overwritten by the next round's writes. Freed tail
        blocks are never registered (registration stops at ``prefilled``) and
        never shared (aliasing only covers prompt blocks), so the truncate is
        a plain decref to the free list. (Windowed -1 holes only ever sit in
        the LEADING region below the write horizon, never in this tail.)"""
        keep = self._blocks_for(len(r.context) + 1)
        if len(r.blocks) > keep:
            r.blocks = self.allocator.truncate(r.blocks, keep)

    def _release_windowed(self, r: Request) -> None:
        """Free blocks no future query of ``r`` can attend. With window W,
        a query at position q attends keys > q - W; every future query sits
        at >= r.prefilled, so block j (tokens [j*bs, (j+1)*bs)) is dead once
        (j+1)*bs <= prefilled - W + 1. A freed entry leaves a -1 hole in the
        LOGICAL table (position p stays at table[p // bs]); the attention
        kernels clamp -1 to block 0 and the window mask makes those keys
        unreachable. Steady-state live blocks per request are thus capped at
        ceil(W / bs) + 1 (paged_cache.windowed_block_cap)."""
        bs = self.pcfg.block_size
        drop = max(0, r.prefilled - self.release_window + 1) // bs
        for j in range(min(drop, len(r.blocks))):
            if r.blocks[j] >= 0:
                self.allocator.free([r.blocks[j]])
                r.blocks[j] = -1

    def _cow_pass(self, decoders, segments) -> bool:
        """Copy-on-write: any block this step will write into whose refcount
        exceeds 1 (a shared prefix block — the aliased-last-block case, or a
        verify segment reaching into one) is replaced by a private
        device-side copy before the packed step runs, so the write can never
        leak into another request's context. Returns False if making room for
        a copy preempted somebody — the caller's decode/segment plan is stale
        and must be recomputed (the swaps done so far remain valid: the
        blocks are now private)."""
        writes: list[tuple[Request, int, int]] = []  # (request, lo blk, hi blk)
        bs = self.pcfg.block_size
        for r in decoders:
            n0 = len(r.context)
            writes.append((r, n0 // bs, (n0 + self._k_for(r)) // bs))
        for r, start, n in segments:
            writes.append((r, start // bs, (start + n - 1) // bs))
        copies: list[tuple[Request, int, int]] = []  # (request, src, dst)
        plan_live = True
        for r, lo, hi in writes:
            if r.state is not RequestState.RUNNING:
                continue  # preempted by an earlier copy's allocation
            for j in range(lo, hi + 1):
                bid = r.blocks[j]
                if self.allocator.refcount(bid) <= 1:
                    continue
                new, preempted = self._alloc_one(r)  # never preempts r itself
                plan_live &= not preempted
                copies.append((r, bid, new))
                r.blocks[j] = new
                self.allocator.free([bid])  # drop r's alias on the original
        # a later allocation may have preempted an earlier copy's owner and
        # recycled its destination block — drop stale pairs so no two copies
        # scatter into the same destination (scatter order is unspecified)
        copies = [(r, s, d) for r, s, d in copies
                  if r.state is RequestState.RUNNING]
        self._c["cow_copies"].add(len(copies))
        if copies:
            # pad (src, dst) to a power-of-two bucket by REPEATING the first
            # pair (duplicate scatters of the same value are idempotent, and
            # no pad row can race a real destination): the jitted copy then
            # compiles per bucket, not per distinct copy count (an
            # unbounded-recompile serving stall)
            cap = 1
            while cap < len(copies):
                cap *= 2
            pad = cap - len(copies)
            src = [s for _, s, _ in copies] + [copies[0][1]] * pad
            dst = [d for _, _, d in copies] + [copies[0][2]] * pad
            self.pools = self._copy_fn(self.pools, np.asarray(src, np.int32),
                                       np.asarray(dst, np.int32))
        return plan_live

    def _grow(self, r: Request, n_tokens: int = 1) -> None:
        """Guarantee blocks for positions ``len(context) .. len(context) +
        n_tokens - 1`` (the cells about to be written — one decode token, or
        a whole verify segment), evicting the youngest other request if the
        pool is dry."""
        while self._blocks_for(len(r.context) + n_tokens) > len(r.blocks):
            got, _ = self._alloc_one(r)
            r.blocks.append(got)

    def _alloc_one(self, r: Request) -> tuple[int, bool]:
        """One block for ``r``, preempting the youngest *other* request until
        the allocator (free list, then cached-prefix LRU) can serve it.
        Returns (block id, whether anything was preempted)."""
        preempted = False
        while True:
            got = self.allocator.alloc(1)
            if got is not None:
                self._g_peak.set_max(self.allocator.occupancy)
                return got[0], preempted
            victims = [v for v in self._running if v is not r]
            if not victims:
                raise RuntimeError(
                    f"request {r.rid} cannot grow: pool of {self.pcfg.n_blocks} "
                    "blocks is exhausted and there is nothing left to preempt"
                )
            self._preempt(victims[-1])
            preempted = True

    def _register_full_blocks(self, r: Request) -> None:
        """Publish every newly-FULL block of ``r`` under its chain hash so
        later admissions can alias it (first writer wins; blocks aliased at
        admission arrive pre-hashed in r.block_hashes and are skipped).
        ``prefilled`` only ever counts verified/committed tokens, so a block
        is published iff every one of its rows holds accepted context — a
        rejected speculative write can never leak into the prefix cache."""
        if not self.allocator.prefix_cache:
            return
        bs = self.pcfg.block_size
        full = r.prefilled // bs  # only blocks whose every token is written
        h = r.block_hashes[-1] if r.block_hashes else self._hash_seed
        while len(r.block_hashes) < full:
            j = len(r.block_hashes)
            h = chain_hash(h, r.context[j * bs : (j + 1) * bs])
            r.block_hashes.append(h)
            self.allocator.register(h, r.blocks[j])

    def _preempt(self, r: Request) -> None:
        # decref tail-first so a whole cached chain ages out leaf-before-root
        # (evicting a root block would orphan its still-cached descendants);
        # windowed -1 holes were already freed at release time
        self.allocator.free([b for b in reversed(r.blocks) if b >= 0])
        r.blocks, r.block_hashes = [], []
        self._slot_free.append(r.slot)
        r.slot = -1
        r.prefilled = 0  # re-admission rewrites (or re-matches) the context
        r.state = RequestState.PREEMPTED
        self._running.remove(r)
        self._queue.appendleft(r)  # front: preserves FCFS completion order
        self._c["preemptions"].add()
        self.telemetry.request_preempted(r.rid)

    def _finish(self, r: Request, results: dict) -> None:
        self.allocator.free([b for b in reversed(r.blocks) if b >= 0])
        r.blocks, r.block_hashes = [], []
        self._slot_free.append(r.slot)
        r.slot = -1
        r.state = RequestState.FINISHED
        self._running.remove(r)
        results[r.rid] = r.output()
        self.telemetry.request_finished(r.rid, n_generated=len(r.generated))

    # ----------------------------------------------------------------- misc
    def _bt_row(self, r: Request) -> np.ndarray:
        row = np.full((self.pcfg.max_blocks_per_seq,), -1, np.int32)
        row[: len(r.blocks)] = r.blocks
        return row

    def _sample(self, logits: jax.Array, r: Request) -> int:
        if self.sc.temperature > 0:
            r.key, sub = jax.random.split(r.key)
            return int(jax.random.categorical(sub, logits / self.sc.temperature))
        return int(jnp.argmax(logits))
