"""Serving engine: the paper's end-to-end quantized inference path.

``make_serve_step``/``make_prefill_step`` build the pure functions the
multi-pod dry-run lowers (decode = one new token against a ring-buffer KV
cache of the shape-specified length). ``ServingEngine`` wraps generation:

* families exporting per-layer **cache policies** (``model.cache_policies()``
  is not None: dense/audio/moe full attention -> ``paged_kv``, SWA archs ->
  ``windowed_paged``, ssm/hybrid -> ``recurrent`` state layers) serve through
  the **paged continuous-batching scheduler** (serving/scheduler.py) — a
  global K-Means-quantizable block pool, per-request block tables, ONE
  packed token-budget step per iteration mixing prefill and decode tokens,
  per-step slot refill, preemption-by-eviction, and refcounted
  **prefix-sharing** of content-hashed blocks with copy-on-write
  (``ServeConfig.prefix_cache``; auto-disabled unless every layer is
  ``paged_kv``). Overflow beyond ``batch_slots`` queues; it is NOT
  recursively chunked.
* families without policies (vlm/multimodal) fall back to the fixed-slot
  ring-buffer batcher, iterating slot-sized batches; left-pad tokens are
  masked out of attention via a per-row ``pad_len`` on the ring caches.

The quantization story end-to-end:
  weights    : K-Means W4/W8 per QuantSpec rule (QLinearParams tree, each
               carrying its resolved QLinearConfig)   — paper §III-A
  activations: K-Means A4/A3 per token + outliers     — paper §III-A/C
  KV cache   : optional K-Means int4 (beyond-paper)   — DESIGN.md §2,
               ring buffer AND paged block pool (serving/README.md)

Apply-time quantization behaviour lives INSIDE the params (see
repro.core.quantspec): the engine no longer carries a quantization config —
build it from a spec's KV policy with ``ServeConfig.from_spec(spec, ...)``.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.quantspec import QuantSpec
from repro.models.model import Model
from repro.serving.speculative import SpeculativeConfig

__all__ = ["ServeConfig", "make_prefill_step", "make_serve_step", "ServingEngine"]


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    cache_len: int = 4096  # max context per request (prompt + generated)
    cache_dtype: str = "bfloat16"
    kv_quant: bool = False
    temperature: float = 0.0  # 0 => greedy
    quantized: bool = True  # serve QLinearParams (False = fp baseline)
    # paged continuous-batching scheduler (attention-cache families)
    paged: bool = True  # False forces the fixed-slot ring-buffer path
    block_size: int = 16  # tokens per KV block
    n_blocks: int = 0  # pool size per layer; 0 -> slots * ceil(cache_len/block_size)
    prefill_chunk: int = 32  # prefill share of the default token budget
    token_budget: int = 0  # packed-step rows; 0 -> slots + prefill_chunk
    # prefix sharing: refcounted content-hashed blocks — admissions alias a
    # prompt's longest cached full-block prefix (prefill skipped for those
    # tokens) with copy-on-write on shared partial blocks; token-identical
    # to prefix_cache=False on greedy decode (serving/README.md)
    prefix_cache: bool = True
    # tokens packed per kernel segment row in the packed step (one
    # block-table gather per ROW, not per token); 1 = the flat layout, which
    # is also what keeps speculative greedy bit-identical to non-speculative
    # greedy (same forward shapes)
    seg_width: int = 1
    # speculative decoding: draft k tokens with a low-bit draft model, verify
    # k+1 positions per packed step (serving/speculative.py). None = off.
    # Token-identical to non-speculative greedy; greedy-only (temperature
    # configs raise until the rejection-sampling hook is implemented).
    speculative: SpeculativeConfig | None = None
    # telemetry (serving/telemetry.py): "metrics" (default — counters, gauges,
    # SLO histograms, step ring), "trace" (adds per-request timelines + spans
    # for the Perfetto export), "quality" (trace + the quantization-numerics
    # probes of core/numerics — sampled probed packed steps, drift alarms,
    # shadow-reference quality checks; the only level allowed to recompile),
    # "off" (null object: zero per-token work and an untouched packed-step
    # jaxpr), or a TelemetryConfig for ring/sampling knobs. Every level
    # compiles the same packed step, with the same device scopes; the step's
    # host/device split comes from the scheduler's wait for its logits
    telemetry: object = "metrics"

    @classmethod
    def from_spec(cls, spec: QuantSpec, **kw) -> "ServeConfig":
        """Serving config whose KV-cache treatment follows the spec's
        first-class kv policy (kv_bits -> int4 pool, kv_dtype -> fp pool)."""
        kw.setdefault("kv_quant", spec.kv_bits is not None)
        kw.setdefault("cache_dtype", spec.kv_dtype)
        return cls(**kw)


def make_prefill_step(model: Model, sc: ServeConfig) -> Callable:
    """prefill(params, caches, batch) -> (first_token (B,), caches, logits)."""

    def prefill(params, caches, batch: dict):
        s = batch["tokens"].shape[1]
        positions = jnp.arange(s, dtype=jnp.int32)
        out = model.apply(params, batch, positions=positions, caches=caches,
                          last_only=True)
        next_tok = jnp.argmax(out.logits[:, -1, : model.cfg.vocab_size], axis=-1)
        return next_tok.astype(jnp.int32), out.caches, out.logits[:, -1]

    return prefill


def make_serve_step(model: Model, sc: ServeConfig) -> Callable:
    """serve_step(params, caches, tokens (B,1), pos ()) -> (next (B,), caches, logits).

    This is the function the decode_32k / long_500k dry-run cells lower:
    one token in, KV cache of the assigned context length, one token out.
    ``logits`` (B, vocab) are this step's outputs, so temperature sampling
    draws from the CURRENT distribution (not stale prefill logits).
    """

    def serve_step(params, caches, tokens: jax.Array, pos: jax.Array):
        positions = pos[None].astype(jnp.int32)
        batch = {"tokens": tokens}
        if model.cfg.family == "vlm":
            batch["image_embeds"] = jnp.zeros(
                (tokens.shape[0], model.cfg.n_img_tokens, model.cfg.d_model),
                jnp.dtype(model.cfg.compute_dtype),
            )
        out = model.apply(params, batch, positions=positions, caches=caches)
        logits = out.logits[:, -1, : model.cfg.vocab_size]
        next_tok = jnp.argmax(logits, axis=-1)
        return next_tok.astype(jnp.int32), out.caches, logits

    return serve_step


def _attach_pad_lens(caches, pad_lens: jax.Array):
    """Insert a per-row ``pad_len`` into every ring attention-cache dict.

    A cache dict is recognized by its ``slot_pos`` leaf; stacked caches
    (leading scan axes) get the (B,) vector broadcast per layer. SSM/RG-LRU
    state dicts carry no ``slot_pos`` and pass through untouched (left-pad
    pollution of recurrent state is inherent to the fixed-slot batcher).
    """
    if isinstance(caches, dict):
        if "slot_pos" in caches:
            lead = caches["slot_pos"].shape[:-1]  # (), (L,) or (G, n_self)
            return caches | {
                "pad_len": jnp.broadcast_to(pad_lens, (*lead, pad_lens.shape[0]))
            }
        return {k: _attach_pad_lens(v, pad_lens) for k, v in caches.items()}
    if isinstance(caches, list):
        return [_attach_pad_lens(c, pad_lens) for c in caches]
    return caches


class ServingEngine:
    """Batched generation over ``batch_slots`` request slots.

    Paged-capable models get true continuous batching (see module docstring);
    the rest get the ring-buffer batcher with iterative (non-recursive)
    slot-sized chunking. Both paths sample each step from that step's logits.
    """

    def __init__(self, model: Model, params, sc: ServeConfig, batch_slots: int = 8,
                 draft=None, calib_stats=None, shadow_params=None):
        """``draft`` (speculative configs): a prepared draft model —
        ``(model, params)``, ``(model, params, spec)``, or the
        :class:`~repro.core.artifact.QuantizedArtifact` tuple. When omitted,
        ``sc.speculative.draft_artifact`` is loaded from disk (the
        production path: quantize the draft once, serve it everywhere).

        ``calib_stats`` / ``shadow_params`` feed the quality-observability
        layer (``telemetry="quality"``; see Scheduler): per-tap calibration
        activation stats (``core.artifact.load_calib_stats``) for drift
        scoring, and the shadow-reference parameter tree (None = serve
        params, the self-referencing probe)."""
        from repro.serving.telemetry import make_telemetry

        self.model, self.sc, self.slots = model, sc, batch_slots
        self.params = params
        self.telemetry = make_telemetry(sc.telemetry)
        self.telemetry.watch_compiles(self)
        policies = model.cache_policies()
        self.paged = sc.paged and policies is not None
        if self.paged and sc.speculative is not None \
                and any(p.kind == "recurrent" for p in policies):
            # recurrent layers need each verify segment (k+1 cells) in ONE
            # grid row; widen seg_width for the user instead of raising
            min_w = sc.speculative.k + 1
            if sc.seg_width < min_w:
                sc = dataclasses.replace(sc, seg_width=min_w)
                self.sc = sc
        if self.paged:
            from repro.serving.scheduler import Scheduler

            if sc.speculative is not None and draft is None:
                if sc.speculative.draft_artifact is None:
                    raise ValueError(
                        "ServeConfig.speculative needs a draft model: set "
                        "speculative.draft_artifact or pass draft=(model, "
                        "params[, spec]) to the engine"
                    )
                from repro.serving.speculative import load_draft

                draft = load_draft(sc.speculative.draft_artifact)
            self.scheduler = Scheduler(model, params, sc, slots=batch_slots,
                                       draft=draft, telemetry=self.telemetry,
                                       calib_stats=calib_stats,
                                       shadow_params=shadow_params)
        else:
            if sc.speculative is not None:
                raise ValueError(
                    "speculative decoding needs the paged scheduler "
                    "(paged=True and a paged-capable model family)"
                )
            self.scheduler = None
            self._prefill = jax.jit(make_prefill_step(model, sc))
            self._step = jax.jit(make_serve_step(model, sc))
            # fallback counters through the same registry as the paged path
            tel = self.telemetry
            self._fc = {k: tel.counter(f"serving_fallback_{k}") for k in (
                "prefills", "steps", "tokens", "prompt_tokens", "pad_tokens")}

    @property
    def stats(self) -> dict:
        """Serving counters. Paged path: the scheduler's dict (packed-step /
        preemption accounting plus prefix-cache hits, tokens of prefill
        skipped, copy-on-write copies, and cached-prefix evictions; under a
        speculative config also the draft forwards run and the acceptance
        rate — accepted / drafted tokens). The fixed-slot fallback reports
        its own batch counters (prefills, decode steps, tokens served, and
        the pad-row fraction of prefill cells) from the same registry."""
        if self.scheduler is None:
            d = {k: c.value for k, c in self._fc.items()}
            d["pad_fraction"] = d["pad_tokens"] / max(1, d["prompt_tokens"])
            return d
        d = dict(self.scheduler.stats,
                 prefix_evictions=self.scheduler.allocator.evictions,
                 prefix_blocks_cached=self.scheduler.allocator.n_cached)
        if self.scheduler.draft is not None:
            d["draft_steps"] = self.scheduler.draft.steps
            d["acceptance_rate"] = (d["accepted_tokens"]
                                    / max(1, d["drafted_tokens"]))
        return d

    def snapshot(self) -> dict:
        """JSON-able dump of every telemetry metric (see Telemetry.snapshot)."""
        return self.telemetry.snapshot()

    def export_chrome_trace(self, path):
        """Write a Chrome/Perfetto trace-event JSON file; open at
        ui.perfetto.dev. Richest under ``telemetry="trace"``."""
        return self.telemetry.export_chrome_trace(path)

    def generate(
        self, prompts: list[list[int]], max_new_tokens: int | list[int] = 32,
        eos_id: int | None = None, seed: int = 0,
    ) -> list[list[int]]:
        """Generate for every prompt; returns per-prompt token lists of
        exactly its max_new_tokens (eos-padded after early stop).
        ``max_new_tokens`` may be per-request (paged scheduler path only)."""
        budgets = (max_new_tokens if isinstance(max_new_tokens, list)
                   else [max_new_tokens] * len(prompts))
        if len(budgets) != len(prompts):
            raise ValueError("per-request max_new_tokens must match prompts")
        if self.paged:
            rids = [self.scheduler.submit(p, n, eos_id, seed, salt=i)
                    for i, (p, n) in enumerate(zip(prompts, budgets))]
            results = self.scheduler.run()
            return [results[r] for r in rids]
        if isinstance(max_new_tokens, list):
            raise ValueError("per-request budgets need the paged scheduler")
        out: list[list[int]] = []
        for i in range(0, len(prompts), self.slots):  # iterative, not recursive
            out += self._generate_batch(prompts[i : i + self.slots],
                                        max_new_tokens, eos_id, seed)
        return out

    def _generate_batch(
        self, prompts: list[list[int]], max_new_tokens: int, eos_id: int | None,
        seed: int,
    ) -> list[list[int]]:
        b = len(prompts)
        plen = max(len(p) for p in prompts)
        toks = jnp.array(
            [[0] * (plen - len(p)) + list(p) for p in prompts], dtype=jnp.int32
        )  # left-pad so all prompts end at the same position
        caches = self.model.init_caches(
            b, self.sc.cache_len, jnp.dtype(self.sc.cache_dtype), quantized=self.sc.kv_quant
        )
        # pad tokens land in the KV cache at positions [0, pad_len) — attach
        # the per-row pad length so attention masks them (they used to be
        # attended as real context, skewing short prompts in mixed batches)
        pads = jnp.array([plen - len(p) for p in prompts], jnp.int32)
        caches = _attach_pad_lens(caches, pads)
        self._fc["prefills"].add()
        self._fc["prompt_tokens"].add(b * plen)
        self._fc["pad_tokens"].add(sum(plen - len(p) for p in prompts))
        with self.telemetry.annotate("fallback_prefill"):
            tok, caches, logits = self._prefill(self.params, caches,
                                                {"tokens": toks, **self._img(b)})
        key = jax.random.PRNGKey(seed)
        done = jnp.zeros((b,), bool)
        if self.sc.temperature > 0:
            key, sub = jax.random.split(key)
            tok = jax.random.categorical(
                sub, logits[:, : self.model.cfg.vocab_size] / self.sc.temperature, axis=-1
            ).astype(jnp.int32)
        outs = [tok]
        pos = plen
        for _ in range(max_new_tokens - 1):
            with self.telemetry.annotate("fallback_step"):
                tok, caches, logits = self._step(self.params, caches,
                                                 tok[:, None], jnp.int32(pos))
            self._fc["steps"].add()
            if self.sc.temperature > 0:
                key, sub = jax.random.split(key)
                tok = jax.random.categorical(
                    sub, logits / self.sc.temperature, axis=-1
                ).astype(jnp.int32)
            if eos_id is not None:
                done = done | (tok == eos_id)
                tok = jnp.where(done, eos_id, tok)
            outs.append(tok)
            pos += 1
            if eos_id is not None and bool(done.all()):
                break
        gen = jnp.stack(outs, axis=1)
        self._fc["tokens"].add(b * len(outs))
        rows = [list(map(int, row)) for row in gen]
        pad = eos_id if eos_id is not None else 0
        return [row + [pad] * (max_new_tokens - len(row)) for row in rows]

    def _img(self, b: int) -> dict:
        if self.model.cfg.family != "vlm":
            return {}
        return {
            "image_embeds": jnp.zeros(
                (b, self.model.cfg.n_img_tokens, self.model.cfg.d_model),
                jnp.dtype(self.model.cfg.compute_dtype),
            )
        }
