"""Training substrate.

``make_train_step(model, tc)`` builds a pure jit-able step:

  * cross-entropy next-token loss (fp32 logits, optional z-loss) + MoE aux
  * gradient accumulation over ``tc.microbatches`` via ``lax.scan`` — the
    memory knob that makes 1M-token global batches compile per-device
  * AdamW update with clipping + schedule
  * optional int8 gradient compression on the DP all-reduce
    (repro.distributed.collectives; off by default, tested separately)

``Trainer`` adds the operational shell: data pipeline, checkpoint/auto-resume
(params, opt state, data-iterator state, step), straggler monitoring.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.models.model import Model
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update, cosine_schedule

__all__ = ["TrainConfig", "make_train_step", "make_eval_step", "loss_fn", "Trainer"]


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    optimizer: AdamWConfig = AdamWConfig()
    microbatches: int = 1
    z_loss: float = 1e-4
    aux_weight: float = 0.01  # MoE load-balance loss weight
    warmup_steps: int = 100
    total_steps: int = 10_000
    checkpoint_every: int = 500
    grad_compression: bool = False


_CE_CHUNK = 4096  # tokens per CE chunk (memory knob; see EXPERIMENTS §Perf C2)


def _chunked_ce(hidden, head_w, labels, z_loss: float):
    """Cross-entropy from hidden states in token chunks under remat.

    Never materializes the full (tokens, vocab) logits: the f32 logits +
    softmax backward of a 256k-vocab head cost ~8 GB/device on the 104B train
    cell before this (§Perf iteration C2). Each chunk's logits are transient
    (chunk x vocab_shard); jax.checkpoint recomputes them in backward.
    """
    d = hidden.shape[-1]
    h = hidden.reshape(-1, d)
    y = labels.reshape(-1)
    t = h.shape[0]
    chunk = min(_CE_CHUNK, t)
    pad = (-t) % chunk
    valid = jnp.pad(jnp.ones((t,), jnp.float32), (0, pad))
    if pad:
        h = jnp.pad(h, ((0, pad), (0, 0)))
        y = jnp.pad(y, (0, pad))
    n = h.shape[0] // chunk

    @jax.checkpoint
    def body(carry, xs):
        hc, yc, vc = xs
        logits = (hc.astype(jnp.float32)) @ head_w.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, yc[:, None], axis=-1)[:, 0]
        ce_sum = jnp.sum((lse - ll) * vc)
        z_sum = jnp.sum(jnp.square(lse) * vc)
        return (carry[0] + ce_sum, carry[1] + z_sum), None

    (ce_sum, z_sum), _ = jax.lax.scan(
        body, (jnp.zeros(()), jnp.zeros(())),
        (h.reshape(n, chunk, d), y.reshape(n, chunk), valid.reshape(n, chunk)),
    )
    ce = ce_sum / t
    return ce, ce + z_loss * (z_sum / t)


def loss_fn(model: Model, params, batch: dict, tc: TrainConfig):
    """batch["tokens"]: (B, S+1). Returns (loss, metrics)."""
    from repro.models.model import head_matrix

    tokens = batch["tokens"]
    inp = {**batch, "tokens": tokens[:, :-1]}
    labels = tokens[:, 1:]
    out = model.apply(params, inp, return_hidden_only=True)
    ce, loss = _chunked_ce(out.hidden, head_matrix(model, params), labels, tc.z_loss)
    if out.aux_loss is not None:
        loss = loss + tc.aux_weight * out.aux_loss
    return loss, {"ce": ce, "aux": out.aux_loss if out.aux_loss is not None else 0.0}


def make_train_step(model: Model, tc: TrainConfig) -> Callable:
    """Returns train_step(state, batch) -> (state, metrics). state:
    {"params", "opt", "compress_err"?}. batch leaves have leading global-batch
    dim divisible by tc.microbatches."""
    lr_fn = cosine_schedule(tc.optimizer.lr, tc.warmup_steps, tc.total_steps)

    def micro_grads(params, batch):
        return jax.value_and_grad(lambda p: loss_fn(model, p, batch, tc), has_aux=True)(params)

    def train_step(state, batch):
        params = state["params"]
        n = tc.microbatches
        if n > 1:
            # microbatch i = rows i, i+n, i+2n, ...: splitting the batch axis
            # as (B/n, n) keeps its "batch" sharding on the B/n part, so the
            # scanned axis is replicated (a scan may not slice a sharded axis)
            micro = jax.tree.map(
                lambda x: jnp.swapaxes(
                    x.reshape(x.shape[0] // n, n, *x.shape[1:]), 0, 1), batch)

            def acc_body(carry, mb):
                gacc, lacc = carry
                (l, _), g = micro_grads(params, mb)
                return (jax.tree.map(jnp.add, gacc, g), lacc + l), None

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (gsum, lsum), _ = jax.lax.scan(acc_body, (g0, jnp.zeros(())), micro)
            grads = jax.tree.map(lambda g: g / n, gsum)
            loss = lsum / n
        else:
            (loss, _), grads = micro_grads(params, batch)

        if tc.grad_compression and "compress_err" in state:
            from repro.distributed.collectives import compress_decompress_tree

            grads, new_err = compress_decompress_tree(grads, state["compress_err"])
        else:
            new_err = state.get("compress_err")

        lr = lr_fn(state["opt"]["step"] + 1)  # +1: step 0 would warm up to lr=0 (no-op step)
        new_params, new_opt, om = adamw_update(grads, state["opt"], params, tc.optimizer, lr)
        new_state = {"params": new_params, "opt": new_opt}
        if new_err is not None:
            new_state["compress_err"] = new_err
        return new_state, {"loss": loss, "lr": lr, **om}

    return train_step


def make_eval_step(model: Model, tc: TrainConfig) -> Callable:
    def eval_step(params, batch):
        loss, metrics = loss_fn(model, params, batch, tc)
        return {"loss": loss, **metrics}

    return eval_step


def init_train_state(model: Model, key, tc: TrainConfig) -> dict:
    params = model.init(key)
    state = {"params": params, "opt": adamw_init(params)}
    if tc.grad_compression:
        from repro.distributed.collectives import init_error_state

        state["compress_err"] = init_error_state(params)
    return state


class Trainer:
    """Operational training shell with fault tolerance.

    - auto-resume: restores (params, opt, pipeline state, step) from the
      latest valid checkpoint in ``ckpt_dir``
    - checkpoint cadence per TrainConfig + final checkpoint on exit
    - straggler monitor: flags steps slower than ``straggler_factor`` x the
      running median (on real clusters this triggers the elastic re-mesh path
      in repro.distributed.fault_tolerance)
    """

    def __init__(self, model: Model, tc: TrainConfig, pipeline, ckpt_dir: str | None = None,
                 seed: int = 0):
        from repro.checkpoint.checkpointer import CheckpointManager
        from repro.distributed.fault_tolerance import StepMonitor

        self.model, self.tc, self.pipeline = model, tc, pipeline
        self.train_step = jax.jit(make_train_step(model, tc))
        self.state = init_train_state(model, jax.random.PRNGKey(seed), tc)
        self.step = 0
        self.monitor = StepMonitor()
        self.ckpt = CheckpointManager(ckpt_dir) if ckpt_dir else None
        if self.ckpt is not None:
            restored = self.ckpt.restore_latest(
                {"state": self.state, "data": self.pipeline.state(), "step": 0}
            )
            if restored is not None:
                self.state = restored["state"]
                self.pipeline.restore(restored["data"])
                self.step = int(restored["step"])

    def run(self, num_steps: int, log_every: int = 10, log: Callable[[str], Any] = print):
        target = self.step + num_steps
        while self.step < target:
            batch = {k: jnp.asarray(v) for k, v in self.pipeline.next_batch().items()}
            t0 = time.perf_counter()
            self.state, metrics = self.train_step(self.state, batch)
            jax.block_until_ready(metrics["loss"])
            dt = time.perf_counter() - t0
            self.monitor.record(dt)
            self.step += 1
            if self.step % log_every == 0:
                log(
                    f"step {self.step} loss {float(metrics['loss']):.4f} "
                    f"lr {float(metrics['lr']):.2e} dt {dt*1e3:.0f}ms"
                    + (" [STRAGGLER]" if self.monitor.is_straggler(dt) else "")
                )
            if self.ckpt is not None and self.step % self.tc.checkpoint_every == 0:
                self._save()
        if self.ckpt is not None:
            self._save()
        return self.state

    def _save(self):
        self.ckpt.save(
            {"state": self.state, "data": self.pipeline.state(), "step": self.step},
            step=self.step,
        )
