"""Unified decoder LM covering dense / MoE / hybrid(Jamba) / RWKV / VLM
architectures, driven entirely by ModelConfig.block_pattern.

Layers are scanned over *pattern cycles* (one cycle = one period of
block_pattern, e.g. Jamba's [attn, mamba x7]); parameters are stacked over
cycles so the HLO stays compact for 94-layer models. Remat policy wraps the
cycle body.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.layers import attention, common, mamba, mlp, moe, rwkv
from repro.layers.common import Accum, Compute
from repro.sharding.rules import constrain


@dataclasses.dataclass(frozen=True)
class RunFlags:
    remat: str = "dots"            # "none" | "full" | "dots"
    use_flash_decode: bool = False
    use_mamba_kernel: bool = False
    use_rwkv_kernel: bool = False
    logits_dtype: str = "bfloat16"
    q_chunk: int = 512             # streaming-attention tile (hillclimb lever)
    kv_chunk: int = 1024


def _vocab_padded(cfg, mesh=None, rules=None):
    mult = 128
    if mesh is not None and rules is not None and rules.tp in getattr(
            mesh, "axis_names", ()):
        mult = max(mult, mesh.shape[rules.tp])
    return common.pad_vocab(cfg.vocab, mult)


def n_cycles(cfg):
    pat = cfg.block_pattern
    assert cfg.n_layers % len(pat) == 0, (cfg.n_layers, pat)
    return cfg.n_layers // len(pat)


def _block_is_moe(cfg, j):
    m = cfg.moe
    return m is not None and (j % m.every) == (m.every - 1)


def _init_block(key, cfg, kind, j):
    ks = jax.random.split(key, 4)
    if kind == "attn":
        p = {"ln1": common.init_rmsnorm(cfg.d_model),
             "attn": attention.init(ks[0], cfg),
             "ln2": common.init_rmsnorm(cfg.d_model)}
    elif kind == "mamba":
        p = {"ln1": common.init_rmsnorm(cfg.d_model),
             "mamba": mamba.init(ks[0], cfg),
             "ln2": common.init_rmsnorm(cfg.d_model)}
    elif kind == "rwkv":
        return {"ln1": common.init_rmsnorm(cfg.d_model),
                "tm_cm": rwkv.init(ks[0], cfg),
                "ln2": common.init_rmsnorm(cfg.d_model)}
    else:
        raise ValueError(kind)
    if _block_is_moe(cfg, j):
        p["moe"] = moe.init(ks[1], cfg)
        if cfg.moe.dense_residual:
            p["ffn"] = mlp.init(ks[2], cfg)
    else:
        p["ffn"] = mlp.init(ks[2], cfg)
    return p


def _block_logical(cfg, kind, j):
    if kind == "rwkv":
        return {"ln1": {"scale": (None,)}, "tm_cm": rwkv.logical_axes(cfg),
                "ln2": {"scale": (None,)}}
    la = {"ln1": {"scale": (None,)}, "ln2": {"scale": (None,)}}
    if kind == "attn":
        la["attn"] = attention.logical_axes(cfg)
    else:
        la["mamba"] = mamba.logical_axes(cfg)
    if _block_is_moe(cfg, j):
        la["moe"] = moe.logical_axes(cfg)
        if cfg.moe.dense_residual:
            la["ffn"] = mlp.logical_axes(cfg)
    else:
        la["ffn"] = mlp.logical_axes(cfg)
    return la


def init(key, cfg, mesh=None, rules=None):
    Vp = _vocab_padded(cfg, mesh, rules)
    D = cfg.d_model
    k_emb, k_head, k_blocks = jax.random.split(key, 3)
    nc = n_cycles(cfg)

    def one_cycle(k):
        ks = jax.random.split(k, len(cfg.block_pattern))
        return {f"blk{j}": _init_block(ks[j], cfg, kind, j)
                for j, kind in enumerate(cfg.block_pattern)}

    groups = jax.vmap(one_cycle)(jax.random.split(k_blocks, nc))
    return {
        "embed": common.dense_init(k_emb, Vp, D, scale=1.0),
        "groups": groups,
        "final_norm": common.init_rmsnorm(D),
        "lm_head": common.dense_init(k_head, D, Vp),
    }


def logical(cfg):
    cyc = {f"blk{j}": _block_logical(cfg, kind, j)
           for j, kind in enumerate(cfg.block_pattern)}
    # prepend the stacked-cycles axis to every leaf
    cyc = jax.tree.map(lambda t: (None,) + t, cyc,
                       is_leaf=lambda x: isinstance(x, tuple) and all(
                           isinstance(e, (str, type(None))) for e in x))
    return {"embed": ("vocab", "fsdp"), "groups": cyc,
            "final_norm": {"scale": (None,)}, "lm_head": ("fsdp", "vocab")}


# ---------------------------------------------------------------------------
# caches / states
# ---------------------------------------------------------------------------


def init_cache(cfg, batch: int, max_len: int, mesh=None, rules=None):
    """Stacked (over cycles) per-block decode state."""
    nc = n_cycles(cfg)

    def one(j, kind):
        if kind == "attn":
            return attention.init_cache(cfg, batch, max_len)
        if kind == "mamba":
            return mamba.init_state(cfg, batch)
        if kind == "rwkv":
            return rwkv.init_state(cfg, batch)
        raise ValueError(kind)

    def stack(tree):
        return jax.tree.map(lambda x: jnp.broadcast_to(x, (nc,) + x.shape),
                            tree)
    return {f"blk{j}": stack(one(j, kind))
            for j, kind in enumerate(cfg.block_pattern)}


def cache_logical(cfg):
    out = {}
    for j, kind in enumerate(cfg.block_pattern):
        if kind == "attn":
            la = attention.cache_logical()
        elif kind == "mamba":
            la = mamba.state_logical()
        else:
            la = rwkv.state_logical()
        out[f"blk{j}"] = jax.tree.map(
            lambda t: (None,) + t, la,
            is_leaf=lambda x: isinstance(x, tuple) and all(
                isinstance(e, (str, type(None))) for e in x))
    return out


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _run_block(blk, kind, j, h, cfg, rules, mesh, flags, cache, cache_index,
               positions, positions3):
    """Returns (h, aux, new_cache)."""
    aux = None
    new_cache = None
    mode = "decode" if cache is not None and cache_index is not None else \
        "causal"
    if kind == "attn":
        with jax.named_scope("attn"):
            a, nk = attention.apply(
                blk["attn"],
                common.rmsnorm(h, blk["ln1"]["scale"], cfg.norm_eps),
                cfg, rules=rules, mesh=mesh, mode=mode,
                positions=positions, positions3=positions3,
                cache=cache, cache_index=cache_index,
                use_flash_decode=flags.use_flash_decode,
                q_chunk=flags.q_chunk, kv_chunk=flags.kv_chunk)
            h = h + a
        new_cache = nk
    elif kind == "mamba":
        a, ns = mamba.apply(
            blk["mamba"], common.rmsnorm(h, blk["ln1"]["scale"], cfg.norm_eps),
            cfg, rules=rules, mesh=mesh, state=cache,
            use_kernel=flags.use_mamba_kernel)
        h = h + a
        new_cache = ns
    elif kind == "rwkv":
        x = common.rmsnorm(h, blk["ln1"]["scale"], cfg.norm_eps)
        st = cache
        y, shift, wkv_s = rwkv.time_mix(
            blk["tm_cm"]["tm"], x, cfg,
            state_shift=None if st is None else st["tm_shift"],
            state_wkv=None if st is None else st["wkv"],
            rules=rules, mesh=mesh, use_kernel=flags.use_rwkv_kernel)
        h = h + y
        x2 = common.rmsnorm(h, blk["ln2"]["scale"], cfg.norm_eps)
        y2, shift2 = rwkv.channel_mix(
            blk["tm_cm"]["cm"], x2, cfg,
            state_shift=None if st is None else st["cm_shift"])
        h = h + y2
        if st is not None:
            new_cache = {"tm_shift": shift, "wkv": wkv_s, "cm_shift": shift2}
        return h, aux, new_cache
    # ffn / moe sub-block (attn & mamba kinds)
    with jax.named_scope("mlp"):
        x2 = common.rmsnorm(h, blk["ln2"]["scale"], cfg.norm_eps)
        if "moe" in blk:
            f, aux = moe.apply(blk["moe"], x2, cfg, rules=rules, mesh=mesh)
            if "ffn" in blk:  # arctic dense residual in parallel
                f = f + mlp.apply(blk["ffn"], x2, cfg, rules=rules,
                                  mesh=mesh)
        else:
            f = mlp.apply(blk["ffn"], x2, cfg, rules=rules, mesh=mesh)
        h = h + f
    return h, aux, new_cache


def _cycle(h, group, cfg, rules, mesh, flags, caches, cache_index, positions,
           positions3):
    aux_total = jnp.zeros((), Accum)
    new_caches = {}
    for j, kind in enumerate(cfg.block_pattern):
        cache_j = None if caches is None else caches[f"blk{j}"]
        h, aux, nc = _run_block(group[f"blk{j}"], kind, j, h, cfg, rules,
                                mesh, flags, cache_j, cache_index, positions,
                                positions3)
        if aux is not None:
            aux_total = aux_total + aux.mean().astype(Accum)
        if nc is not None:
            new_caches[f"blk{j}"] = nc
    return h, aux_total, new_caches


def embed_apply(params, tokens, cfg, *, rules=None, mesh=None,
                embeds: Optional[jax.Array] = None):
    """The forward's embedding stage alone: token lookup (+ optional
    frontend embeds prepended). The entry segment of the backward-segmented
    train step — its VJP is the embedding-table grad bucket."""
    with jax.named_scope("embed"):
        h = jnp.take(params["embed"], tokens, axis=0)
        if embeds is not None:
            h = jnp.concatenate([embeds.astype(h.dtype), h], axis=1)
        return constrain(h, ("batch", None, None), rules, mesh)


def _mrope_positions3(cfg, B, T, cache_index, positions3):
    if cfg.rope == "mrope" and positions3 is None:
        base = cache_index if cache_index is not None else 0
        if getattr(base, "ndim", 0):
            # per-slot decode indices: each row's positions start at its own
            # true length (continuous-batching mixed-length ticks)
            pos = jnp.arange(T)[None] + base[:, None]
        else:
            pos = jnp.broadcast_to(jnp.arange(T)[None] + base, (B, T))
        positions3 = common.text_positions3(pos)
    return positions3


def _remat_wrap(scan_body, flags: RunFlags):
    if flags.remat == "full":
        return jax.checkpoint(scan_body,
                              policy=jax.checkpoint_policies.nothing_saveable)
    if flags.remat == "dots":
        return jax.checkpoint(
            scan_body,
            policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    return scan_body


def segment_apply(params, h, cfg, lo: int, hi: int, *, rules=None,
                  mesh=None, flags: RunFlags = RunFlags(),
                  positions3: Optional[jax.Array] = None):
    """Run pattern cycles ``[lo, hi)`` of the stacked groups on hidden
    state ``h`` (training path: no caches). Returns ``(h, aux_sum)``.

    This is the forward's scan restricted to a static cycle window — the
    unit the backward-segmented train step takes a per-bucket VJP of, so
    bucket i's allreduce can start while cycles ``[0, lo)`` are still
    running backward. ``segment_apply(params, h, cfg, 0, n_cycles(cfg))``
    is the whole trunk (and is exactly what :func:`forward` runs)."""
    B, T, _ = h.shape
    positions3 = _mrope_positions3(cfg, B, T, None, positions3)
    body = partial(_cycle, cfg=cfg, rules=rules, mesh=mesh, flags=flags,
                   cache_index=None, positions=None, positions3=positions3)

    def scan_body(carry, group):
        h, aux, _ = body(carry, group, caches=None)
        return h, aux

    gslice = jax.tree.map(
        lambda g: jax.lax.slice_in_dim(g, lo, hi, axis=0), params["groups"])
    with jax.named_scope("layers"):
        h, auxs = jax.lax.scan(_remat_wrap(scan_body, flags), h, gslice)
    return h, auxs.sum()


def head_apply(params, h, cfg, *, rules=None, mesh=None,
               flags: RunFlags = RunFlags()):
    """The forward's output stage alone: final norm + LM head. The exit
    segment of the backward-segmented train step — its VJP is the
    (final_norm, lm_head) grad bucket plus the trunk cotangent."""
    with jax.named_scope("head"):
        h = common.rmsnorm(h, params["final_norm"]["scale"], cfg.norm_eps)
        logits = (h @ params["lm_head"]).astype(
            jnp.dtype(flags.logits_dtype))
        return constrain(logits, ("batch", None, "vocab"), rules, mesh)


def forward(params, tokens, cfg, *, rules=None, mesh=None,
            flags: RunFlags = RunFlags(), caches=None, cache_index=None,
            embeds: Optional[jax.Array] = None,
            positions3: Optional[jax.Array] = None):
    """tokens: (B, T) int32. embeds: optional (B, T_p, D) stub-frontend
    embeddings (VLM patches / audio frames) prepended to the token stream.

    Returns (logits (B, T_total, vocab_padded), aux_loss scalar, new_caches).
    """
    h = embed_apply(params, tokens, cfg, rules=rules, mesh=mesh,
                    embeds=embeds)
    B, T, D = h.shape

    positions = None
    positions3 = _mrope_positions3(cfg, B, T, cache_index, positions3)

    body = partial(_cycle, cfg=cfg, rules=rules, mesh=mesh, flags=flags,
                   cache_index=cache_index, positions=positions,
                   positions3=positions3)

    if caches is None:
        def scan_body(carry, group):
            h = carry
            h, aux, _ = body(h, group, caches=None)
            return h, aux
        with jax.named_scope("layers"):
            h, auxs = jax.lax.scan(_remat_wrap(scan_body, flags), h,
                                   params["groups"])
        new_caches = None
        aux = auxs.sum()
    else:
        def scan_body(carry, xs):
            h = carry
            group, cache_c = xs
            h, aux, nc = body(h, group, caches=cache_c)
            return h, (aux, nc)
        with jax.named_scope("layers"):
            h, (auxs, new_caches) = jax.lax.scan(
                scan_body, h, (params["groups"], caches))
        aux = auxs.sum()

    logits = head_apply(params, h, cfg, rules=rules, mesh=mesh, flags=flags)
    return logits, aux, new_caches
