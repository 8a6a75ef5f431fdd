"""AdamW + gradient clipping + LR schedules, from scratch (pytree-native).

Optimizer state is fp32 (m, v); params may be bf16 (master copies in fp32
optional via `master_fp32`). The DP gradient sync pairs with the
error-bounded compressed-collective subsystem (``repro.core.compress``
codecs + ``train.manual_step``'s per-bucket ``error_budget``) for
wire-compressed exchange with error feedback.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10000
    min_lr_ratio: float = 0.1
    master_fp32: bool = False
    schedule: str = "cosine"  # "cosine" | "linear" | "constant"


def schedule_lr(cfg: AdamWConfig, step):
    step = step.astype(jnp.float32)
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    if cfg.schedule == "constant":
        decay = 1.0
    else:
        t = jnp.clip((step - cfg.warmup_steps)
                     / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1),
                     0.0, 1.0)
        if cfg.schedule == "cosine":
            decay = cfg.min_lr_ratio + (1 - cfg.min_lr_ratio) * 0.5 * (
                1 + jnp.cos(jnp.pi * t))
        else:
            decay = cfg.min_lr_ratio + (1 - cfg.min_lr_ratio) * (1 - t)
    return cfg.lr * warm * decay


def init(params, cfg: AdamWConfig):
    zeros32 = lambda p: jnp.zeros(p.shape, jnp.float32)
    state = {
        "step": jnp.zeros((), jnp.int32),
        "m": jax.tree.map(zeros32, params),
        "v": jax.tree.map(zeros32, params),
    }
    if cfg.master_fp32:
        state["master"] = jax.tree.map(lambda p: p.astype(jnp.float32),
                                       params)
    return state


def global_norm(tree):
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in leaves))


def clip_by_global_norm(grads, max_norm):
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-9))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale), grads), norm


_NO_DECAY_SUBSTR = ("ln", "norm", "bias", "scale", "mu", "A_log", "D_skip",
                    "dt_bias", "w0", "u")


def _decay_mask(params):
    def mask_path(path, _):
        names = [getattr(k, "key", str(k)) for k in path]
        joined = "/".join(str(n) for n in names).lower()
        return not any(s in joined for s in _NO_DECAY_SUBSTR)
    return jax.tree_util.tree_map_with_path(mask_path, params)


def update(params, grads, state, cfg: AdamWConfig):
    """One AdamW step. Returns (new_params, new_state, metrics)."""
    with jax.named_scope("adamw"):
        return _update(params, grads, state, cfg)


def _update(params, grads, state, cfg: AdamWConfig):
    grads, gnorm = clip_by_global_norm(grads, cfg.grad_clip)
    step = state["step"] + 1
    lr = schedule_lr(cfg, step)
    b1, b2 = cfg.b1, cfg.b2
    bc1 = 1 - b1 ** step.astype(jnp.float32)
    bc2 = 1 - b2 ** step.astype(jnp.float32)
    decay_mask = _decay_mask(params)

    new_m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["m"], grads)
    new_v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * jnp.square(g),
                         state["v"], grads)

    base = state.get("master", params)

    def upd(p, m, v, dm):
        p32 = p.astype(jnp.float32)
        u = (m / bc1) / (jnp.sqrt(v / bc2) + cfg.eps)
        if cfg.weight_decay:
            u = u + cfg.weight_decay * p32 * dm
        return p32 - lr * u

    new_base = jax.tree.map(upd, base, new_m, new_v, decay_mask)
    new_params = jax.tree.map(lambda nb, p: nb.astype(p.dtype), new_base,
                              params)
    new_state = {"step": step, "m": new_m, "v": new_v}
    if "master" in state:
        new_state["master"] = new_base
    return new_params, new_state, {"grad_norm": gnorm, "lr": lr}


def state_logical(param_logical, cfg: AdamWConfig):
    """Optimizer state shards exactly like the params (ZeRO semantics)."""
    out = {"step": (), "m": param_logical, "v": param_logical}
    if cfg.master_fp32:
        out["master"] = param_logical
    return out
