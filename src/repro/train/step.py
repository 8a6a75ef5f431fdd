"""Training step: loss, grad, AdamW update — built for pjit over the
production mesh. Microbatch gradient accumulation via lax.scan.

The manual-collective variant (mcoll DP sync + int8 compression) lives in
manual_step.py; this module is the pjit/GSPMD path used by the dry-run."""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.layers.common import Accum
from repro.models import decoder, encdec
from repro.models.decoder import RunFlags
from repro.optim import adamw
from repro.sharding.rules import constrain


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    optimizer: adamw.AdamWConfig = adamw.AdamWConfig()
    microbatches: int = 1
    z_loss: float = 1e-4
    flags: RunFlags = RunFlags()


def cross_entropy(logits, labels, z_loss: float = 0.0):
    """logits (B,S,V) any dtype, labels (B,S) int32 (-1 = masked).

    fp32 log-softmax; returns (mean_loss, n_tokens)."""
    with jax.named_scope("xent"):
        mask = (labels >= 0)
        labels = jnp.maximum(labels, 0)
        lg = logits.astype(Accum)
        lse = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
        nll = lse - gold
        if z_loss:
            nll = nll + z_loss * jnp.square(lse)
        n = jnp.maximum(mask.sum(), 1)
        return jnp.where(mask, nll, 0.0).sum() / n, n


def loss_fn(params, batch, cfg, tcfg: TrainConfig, rules=None, mesh=None):
    flags = tcfg.flags
    if cfg.family == "encdec":
        logits, aux, _ = encdec.forward_train(
            params, batch["frames"], batch["tokens"], cfg,
            rules=rules, mesh=mesh, flags=flags)
    else:
        logits, aux, _ = decoder.forward(
            params, batch["tokens"], cfg, rules=rules, mesh=mesh,
            flags=flags, embeds=batch.get("embeds"))
        if "embeds" in batch and batch["embeds"] is not None:
            # loss only over the token tail (frontend positions are inputs)
            logits = logits[:, batch["embeds"].shape[1]:]
    ce, n = cross_entropy(logits, batch["labels"], tcfg.z_loss)
    moe_w = cfg.moe.aux_loss_weight if cfg.moe else 0.0
    total = ce + moe_w * aux
    return total, {"ce": ce, "aux": aux, "tokens": n}


def train_step(params, opt_state, batch, cfg, tcfg: TrainConfig,
               rules=None, mesh=None):
    """One optimizer step, optionally over `microbatches` grad-accum slices
    (batch dim 0 must divide)."""
    nmb = tcfg.microbatches

    def grads_of(mb):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, mb, cfg, tcfg, rules, mesh)
        return loss, metrics, grads

    if nmb == 1:
        loss, metrics, grads = grads_of(batch)
    else:
        def split(x):
            return x.reshape((nmb, x.shape[0] // nmb) + x.shape[1:])
        mbs = jax.tree.map(split, batch)
        zero_g = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                              params)

        def body(carry, mb):
            acc_loss, acc_g = carry
            loss, metrics, grads = grads_of(mb)
            acc_g = jax.tree.map(lambda a, g: a + g.astype(jnp.float32) / nmb,
                                 acc_g, grads)
            return (acc_loss + loss / nmb, acc_g), metrics

        (loss, grads), metrics = jax.lax.scan(
            body, (jnp.zeros((), Accum), zero_g), mbs)
        metrics = jax.tree.map(lambda x: x[-1], metrics)

    new_params, new_opt, opt_metrics = adamw.update(
        params, grads, opt_state, tcfg.optimizer)
    metrics = dict(metrics, **opt_metrics, loss=loss)
    return new_params, new_opt, metrics


def make_jitted_train_step(cfg, tcfg: TrainConfig, mesh, rules,
                           param_shardings, opt_shardings, batch_shardings,
                           donate: bool = True):
    fn = partial(train_step, cfg=cfg, tcfg=tcfg, rules=rules, mesh=mesh)
    return jax.jit(
        fn,
        in_shardings=(param_shardings, opt_shardings, batch_shardings),
        out_shardings=(param_shardings, opt_shardings, None),
        donate_argnums=(0, 1) if donate else ())
