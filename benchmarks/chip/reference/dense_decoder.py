"""Plain reference of training a dense decoder LM (Llama and Qwen2
families): the forward pass, the loss, its gradients and AdamW, in
straightforward ``jax.numpy``.

It follows the published description and imports nothing of the program:

- pre-norm blocks, RMSNorm ``x / sqrt(mean(x^2) + eps) * w``;
- attention with optional q/k/v biases, rotary embeddings on the two
  halves of each head (``rotate_half``, base ``rope_theta``), grouped
  query heads (query head i reads key/value head ``i // (H / KV)``),
  causal softmax over ``q.k / sqrt(head_dim)``;
- a SwiGLU MLP ``w_down(silu(x w_gate) * (x w_up))``;
- a final RMSNorm and an untied LM head; the loss is the mean token
  cross-entropy plus ``z_loss * logsumexp^2`` (PaLM's z-loss);
- AdamW with global-norm clipping, bias-corrected moments kept in f32 and
  parameters kept in the configuration's dtype (bf16: each update is
  computed in f32 and rounded once to bf16, as training without a master
  copy does).

``precision="f32"`` computes every product in f32 at ``HIGHEST`` matmul
precision. ``precision="fp8"`` is the control: every matmul operand, in
the forward and in both backward products, is rounded to float8 e4m3 with
a per-tensor scale, and accumulated in f32.

Memory stays small enough to run after the program on one chip: the
parameters stay in their stored dtype, and the backward is taken layer by
layer (a scan over layers that recomputes each layer's forward from its
saved input), with the head and loss taken over blocks of rows. Only the
gradients and Adam's moments are held in f32.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, List, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32
_FP8 = jnp.float8_e4m3fn
_FP8_MAX = 448.0


# ---------------------------------------------------------------------------
# products at the stated precision
# ---------------------------------------------------------------------------


def _fake_fp8(x):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, _FP8_MAX / amax, 1.0)
    return (x * scale).astype(_FP8).astype(F32) / scale


@jax.custom_vjp
def _q_in(x):
    """Operand rounding: forward rounds to fp8; the cotangent passes."""
    return _fake_fp8(x)


_q_in.defvjp(lambda x: (_fake_fp8(x), None), lambda _, g: (g,))


@jax.custom_vjp
def _q_out(x):
    """Cotangent rounding: forward passes; the cotangent, which is an
    operand of both backward products, is rounded to fp8."""
    return x


_q_out.defvjp(lambda x: (x, None), lambda _, g: (_fake_fp8(g),))


def product(precision: str) -> Callable:
    """``mm(spec, a, b)``: an einsum at the given precision."""
    if precision == "f32":
        return functools.partial(_einsum)
    if precision == "fp8":
        return lambda spec, a, b: _q_out(_einsum(spec, _q_in(a), _q_in(b)))
    raise ValueError(f"unknown precision {precision!r}")


def _einsum(spec, a, b):
    return jnp.einsum(spec, a, b, precision=lax.Precision.HIGHEST,
                      preferred_element_type=F32)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


def _dims(cfg):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return d, h, cfg["num_key_value_heads"], cfg.get("head_dim") or d // h


def rmsnorm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta):
    """x: (B, T, heads, hd); rotary embedding of positions 0..T-1."""
    t, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def layer(p: Mapping, h, cfg: Mapping, mm: Callable):
    """One pre-norm block; ``p`` holds this layer's f32 weights."""
    d, nh, nkv, hd = _dims(cfg)
    b, t, _ = h.shape
    eps = cfg["rms_norm_eps"]
    x = rmsnorm(h, p["ln1"], eps)
    q = mm("btd,dk->btk", x, p["wq"])
    k = mm("btd,dk->btk", x, p["wk"])
    v = mm("btd,dk->btk", x, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = rope(q.reshape(b, t, nh, hd), cfg["rope_theta"])
    k = rope(k.reshape(b, t, nkv, hd), cfg["rope_theta"])
    v = v.reshape(b, t, nkv, hd)
    rep = nh // nkv
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    s = mm("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = mm("bhqk,bkhd->bqhd", a, v).reshape(b, t, nh * hd)
    h = h + mm("btk,kd->btd", o, p["wo"])
    x = rmsnorm(h, p["ln2"], eps)
    g = mm("btd,df->btf", x, p["w_gate"])
    u = mm("btd,df->btf", x, p["w_up"])
    return h + mm("btf,fd->btd", jax.nn.silu(g) * u, p["w_down"])


def head_loss_sum(fn, lm, h, labels, cfg, mm, z_loss):
    """Sum over the rows' tokens of cross-entropy + z_loss * lse^2."""
    x = rmsnorm(h, fn, cfg["rms_norm_eps"])
    logits = mm("btd,dv->btv", x, lm)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.sum(lse - gold + z_loss * lse * lse)


def _layer_params(plain: Mapping) -> Dict[str, jax.Array]:
    return {k.split(".", 1)[1]: v for k, v in plain.items()
            if k.startswith("layers.")}


def loss_and_grads(plain: Mapping, tokens, labels, cfg: Mapping,
                   precision: str, z_loss: float, row_block: int):
    """(mean loss, f32 gradients) of the plain-layout ``plain`` on one
    batch. The backward is taken layer by layer and the head over blocks
    of ``row_block`` rows, so no f32 copy of the whole model is made."""
    mm = product(precision)
    up = lambda t: jax.tree.map(lambda a: a.astype(F32), t)
    lp = _layer_params(plain)
    b, t = tokens.shape
    n_tok = b * t

    h0 = jnp.take(plain["embed"], tokens, axis=0).astype(F32)

    def fwd(h, pl):
        return layer(up(pl), h, cfg, mm), h

    h_last, h_ins = lax.scan(fwd, h0, lp)

    fn32, lm32 = plain["final_norm"].astype(F32), plain["lm_head"].astype(F32)
    nb = b // row_block

    def head_block(carry, xs):
        loss, dfn, dlm = carry
        hb, lb = xs
        val, vjp = jax.vjp(
            lambda f, w, x: head_loss_sum(f, w, x, lb, cfg, mm, z_loss),
            fn32, lm32, hb)
        gf, gw, gh = vjp(jnp.asarray(1.0 / n_tok, F32))
        return (loss + val, dfn + gf, dlm + gw), gh

    split = lambda a: a.reshape((nb, row_block) + a.shape[1:])
    (loss, dfn, dlm), dh = lax.scan(
        head_block, (jnp.zeros((), F32), jnp.zeros_like(fn32),
                     jnp.zeros_like(lm32)),
        (split(h_last), split(labels)))
    dh = dh.reshape(h_last.shape)

    def bwd(dh, xs):
        pl, h_in = xs
        _, vjp = jax.vjp(lambda p_, x_: layer(p_, x_, cfg, mm), up(pl), h_in)
        dp, dh_in = vjp(dh)
        return dh_in, dp

    dh0, dlayers = lax.scan(bwd, dh, (lp, h_ins), reverse=True)
    demb = jnp.zeros(plain["embed"].shape, F32).at[tokens].add(dh0)
    grads = {"embed": demb, "final_norm": dfn, "lm_head": dlm}
    grads.update({f"layers.{k}": v for k, v in dlayers.items()})
    return loss / n_tok, grads


_NO_DECAY = ("layers.ln1", "layers.ln2", "final_norm", "layers.bq",
             "layers.bk", "layers.bv")


def adamw(plain, grads, m, v, step, opt: Mapping, names: Sequence[str]):
    """One AdamW step (``step`` counts from 1): global-norm clipping, f32
    moments, decoupled weight decay on matrices and the embedding, the
    update rounded once to the parameters' dtype. Returns the new
    parameters and moments and the per-leaf norms of the clipped
    gradient."""
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in grads.values()))
    scale = jnp.minimum(1.0, opt["grad_clip"] / (gnorm + 1e-9))
    b1, b2 = opt["b1"], opt["b2"]
    lr = opt["lr"]
    bc1, bc2 = 1.0 - b1 ** step, 1.0 - b2 ** step
    new_p, new_m, new_v, clipped = {}, {}, {}, {}
    for k in plain:
        g = grads[k] * scale
        clipped[k] = jnp.sqrt(jnp.sum(g * g))
        new_m[k] = b1 * m[k] + (1 - b1) * g
        new_v[k] = b2 * v[k] + (1 - b2) * g * g
        p32 = plain[k].astype(F32)
        u = (new_m[k] / bc1) / (jnp.sqrt(new_v[k] / bc2) + opt["eps"])
        if opt.get("weight_decay") and k not in _NO_DECAY:
            u = u + opt["weight_decay"] * p32
        new_p[k] = (p32 - lr * u).astype(plain[k].dtype)
    return new_p, new_m, new_v, jnp.stack([clipped[n] for n in names])


def leaf_norms(tree: Mapping, names: Sequence[str]):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(tree[n].astype(F32))))
                      for n in names])


class Reference:
    """The reference's compiled programs for one configuration, built
    once and run for as many seeds as asked."""

    def __init__(self, cfg: Mapping, opt: Mapping, z_loss: float,
                 precision: str = "f32", row_block: int = 1):
        self._grad = jax.jit(functools.partial(
            loss_and_grads, cfg=cfg, precision=precision, z_loss=z_loss,
            row_block=row_block))

        @functools.partial(jax.jit, donate_argnums=(1, 2, 3),
                           static_argnums=(5,))
        def update(p, g, m, v, step, names):
            return adamw(p, g, m, v, step, opt, names)

        @functools.partial(jax.jit, static_argnums=(2,))
        def change(a, b, names):
            return leaf_norms({k: a[k].astype(F32) - b[k].astype(F32)
                               for k in names}, names)

        self._update, self._change = update, change

    def train(self, plain0: Mapping, batches: List[Mapping],
              device=None) -> Dict[str, np.ndarray]:
        """Run len(batches) steps from ``plain0`` and return the readings
        the comparison needs: each step's loss, the per-leaf norms of the
        first (clipped) gradient, and the per-leaf norms of the
        parameters' change over all the steps, in the order of
        ``sorted(plain0)``."""
        names = tuple(sorted(plain0))
        put = ((lambda a: jax.device_put(a, device)) if device
               else jnp.asarray)
        p = plain = {k: put(v) for k, v in plain0.items()}
        m = {k: jnp.zeros(v.shape, F32, device=device)
             for k, v in plain.items()}
        v_ = {k: jnp.zeros(v.shape, F32, device=device)
              for k, v in plain.items()}
        losses, first = [], None
        for i, batch in enumerate(batches):
            loss, g = self._grad(p, put(batch["tokens"]),
                                 put(batch["labels"]))
            losses.append(float(loss))
            p, m, v_, clipped = self._update(p, g, m, v_,
                                             jnp.asarray(i + 1, F32), names)
            if i == 0:
                first = np.asarray(clipped)
            del g
        change = np.asarray(self._change(p, plain, names))
        return {"names": list(names), "loss": np.asarray(losses, np.float64),
                "grad_norm": first, "change_norm": change}
