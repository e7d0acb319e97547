"""train_step / prefill_step / serve_step — the functions the launcher jits.

These are the exact computations the dry-run lowers for every
(arch x shape x mesh) cell:
  * train_*   — loss + grad + AdamW update (optionally with microbatch
                gradient accumulation), donated state.
  * prefill_* — full-sequence forward returning logits (batch inference).
  * serve_*   — one-token decode against a KV/SSM cache, donated cache.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import decode_step, forward
from repro.numerics import numerics_scope
from repro.optim import adamw_init, adamw_update, cosine_warmup


@partial(jax.tree_util.register_dataclass,
         data_fields=["params", "opt", "step"], meta_fields=[])
@dataclasses.dataclass
class TrainState:
    params: Any
    opt: Any
    step: jnp.ndarray


def make_train_state(cfg: ModelConfig, key) -> TrainState:
    from repro.models import init_params
    params = init_params(cfg, key)
    return TrainState(params, adamw_init(params), jnp.zeros((), jnp.int32))


def loss_fn(cfg: ModelConfig, params, tokens, targets, extra=None,
            aux_weight: float = 0.01, step=None, *, with_logits: bool = False):
    """``step`` (traced int scalar) feeds the numerics PRNG scope so
    amr_noise draws decorrelate across training steps (repro.numerics.context).

    ``with_logits=True`` returns ``(loss, (aux, logits))`` — lets a single
    differentiated call serve both the gradient and a logits inspection
    (the conformance probes) without a second forward compile."""
    with numerics_scope(step=step):
        logits, aux = forward(cfg, params, tokens, extra)
    ll = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(ll, targets[..., None], axis=-1)[..., 0]
    loss = nll.mean() + aux_weight * aux
    return (loss, (aux, logits)) if with_logits else (loss, aux)


def make_train_step(cfg: ModelConfig, *, peak_lr: float = 3e-4, warmup: int = 100,
                    total_steps: int = 10_000, microbatch: int | None = None):
    """Returns train_step(state, batch) -> (state, metrics).

    ``microbatch``: split the global batch into that many sequential
    micro-steps with gradient accumulation (activation memory / pipeline
    trade-off — a §Perf lever).
    """

    def grads_of(params, tokens, targets, extra, step):
        (loss, aux), grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, tokens, targets, extra, step=step),
            has_aux=True)(params)
        return loss, aux, grads

    def train_step(state: TrainState, batch: dict):
        tokens = batch["tokens"]
        targets = batch["targets"]
        extra = batch.get("extra")
        if microbatch and microbatch > 1:
            def mb(carry, xs):
                loss_a, aux_a, acc = carry
                t, y = xs[0], xs[1]
                e = xs[2] if len(xs) > 2 else None
                loss, aux, g = grads_of(state.params, t, y, e, state.step)
                acc = jax.tree.map(jnp.add, acc, g)
                return (loss_a + loss, aux_a + aux, acc), None

            B = tokens.shape[0]
            if B % microbatch:
                raise ValueError(
                    f"global batch size {B} is not divisible by "
                    f"microbatch={microbatch}; pick a microbatch count that "
                    f"divides the batch (e.g. {B} % {microbatch} == 0)")
            mbs = B // microbatch
            resh = lambda x: x.reshape(microbatch, mbs, *x.shape[1:])
            xs = (resh(tokens), resh(targets)) + ((resh(extra),) if extra is not None else ())
            zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            (loss, aux, grads), _ = jax.lax.scan(mb, (0.0, 0.0, zero), xs)
            loss, aux = loss / microbatch, aux / microbatch
            grads = jax.tree.map(lambda g: g / microbatch, grads)
        else:
            loss, aux, grads = grads_of(state.params, tokens, targets, extra,
                                        state.step)

        lr = cosine_warmup(state.step, peak_lr=peak_lr, warmup=warmup, total=total_steps)
        with jax.named_scope("optim.update"):
            params, opt = adamw_update(grads, state.opt, state.params, lr)
        metrics = {"loss": loss, "aux": aux, "lr": lr}
        return TrainState(params, opt, state.step + 1), metrics

    return train_step


def make_grads_step(cfg: ModelConfig):
    """Forward+backward only (one microbatch worth) — the dry-run's unit of
    cost extraction: per-step cost = microbatches x this + optimizer terms
    (launch/roofline.py)."""

    def grads_step(params, batch):
        (_, _), grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, batch["tokens"], batch["targets"],
                              batch.get("extra")), has_aux=True)(params)
        return grads

    return grads_step


def make_prefill_step(cfg: ModelConfig):
    """Prefill returns ONLY the last position's logits (the decode seed).

    Materialising (B, S, vocab) logits for a 32k prefill is ~tens of GB per
    device of pure waste — no serving system does it (measured: gemma3-1b
    prefill peak 100 GB/device before this, <16 GB after)."""

    def prefill_step(params, batch):
        logits, _ = forward(cfg, params, batch["tokens"], batch.get("extra"),
                            last_only=True)
        return logits[:, 0, :]

    return prefill_step


def make_serve_step(cfg: ModelConfig, *, with_logits: bool = False):
    """One greedy decode step over a (possibly slot-batched) cache.

    ``batch`` may carry ``active`` — a (B,) bool continuous-batching slot
    mask threaded through to ``decode_step`` (inactive slots' cache state is
    held bit-for-bit; their outputs are garbage the caller masks off). One
    trace serves every admit/evict pattern: the mask is a traced operand,
    so slots finishing or joining never recompiles.

    ``with_logits=True`` additionally returns the final-position logits
    (float32) — serve_bench uses the raw logit stream for the
    batched-vs-solo bit-exactness gate, which is a strictly stronger check
    than argmax-token equality.
    """

    def serve_step(params, cache, batch):
        logits, cache = decode_step(cfg, params, batch["token"], cache,
                                    batch.get("enc_out"), batch.get("active"))
        last = logits[:, -1]
        next_tok = jnp.argmax(last, axis=-1).astype(jnp.int32)
        if with_logits:
            return next_tok, last.astype(jnp.float32), cache
        return next_tok, cache

    return serve_step
