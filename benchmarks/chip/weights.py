"""Weights of a dense decoder LM, made on the device from the run's seed.

The benchmark makes the weights itself, so that the reference can make the
same ones without taking anything from the program. :func:`init` gives
the plain layout the reference reads (one stacked array per kind of layer
weight); :func:`to_program` and :func:`from_program` map it to and from
the parameter tree of the program's ``models/decoder.py``.
"""
from __future__ import annotations

from typing import Dict, List, Mapping, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def key_from_seed(seed: int) -> jax.Array:
    """A threefry key from a seed of any size (two 32-bit words)."""
    words = np.random.SeedSequence(int(seed) % 2 ** 63).generate_state(2)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32))


def dims(cfg: Mapping) -> Tuple[int, ...]:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // h
    return (cfg["num_hidden_layers"], d, h, cfg["num_key_value_heads"], hd,
            cfg["intermediate_size"], cfg["vocab_size"])


def shapes(cfg: Mapping) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """Plain leaf name -> (shape, init kind), in a fixed order."""
    L, D, H, KV, hd, F, V = dims(cfg)
    out = {"embed": ((V, D), "normal"),
           "layers.ln1": ((L, D), "ones"),
           "layers.wq": ((L, D, H * hd), "normal"),
           "layers.wk": ((L, D, KV * hd), "normal"),
           "layers.wv": ((L, D, KV * hd), "normal"),
           "layers.wo": ((L, H * hd, D), "normal")}
    if cfg.get("qkv_bias"):
        out.update({"layers.bq": ((L, H * hd), "zeros"),
                    "layers.bk": ((L, KV * hd), "zeros"),
                    "layers.bv": ((L, KV * hd), "zeros")})
    out.update({"layers.ln2": ((L, D), "ones"),
                "layers.w_gate": ((L, D, F), "normal"),
                "layers.w_up": ((L, D, F), "normal"),
                "layers.w_down": ((L, F, D), "normal"),
                "final_norm": ((D,), "ones"),
                "lm_head": ((D, V), "normal")})
    return out


def names(cfg: Mapping) -> List[str]:
    return list(shapes(cfg))


def init(key, cfg: Mapping, dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """Plain-layout weights: normal(0, initializer_range) matrices and
    embedding, unit norm scales, zero biases."""
    std = float(cfg.get("initializer_range", 0.02))
    spec = shapes(cfg)
    keys = jax.random.split(key, len(spec))
    out = {}
    for k, (name, (shape, kind)) in zip(keys, spec.items()):
        if kind == "normal":
            out[name] = (jax.random.normal(k, shape, jnp.float32)
                         * std).astype(dtype)
        elif kind == "ones":
            out[name] = jnp.ones(shape, dtype)
        else:
            out[name] = jnp.zeros(shape, dtype)
    return out


_ATTN = ("wq", "wk", "wv", "wo", "bq", "bk", "bv")
_FFN = ("w_gate", "w_up", "w_down")


def to_program(plain: Mapping) -> dict:
    """The plain layout as ``models/decoder.py``'s parameter tree (one
    ``attn`` block per pattern cycle, stacked over layers)."""
    attn = {n: plain[f"layers.{n}"] for n in _ATTN
            if f"layers.{n}" in plain}
    blk = {"ln1": {"scale": plain["layers.ln1"]}, "attn": attn,
           "ln2": {"scale": plain["layers.ln2"]},
           "ffn": {n: plain[f"layers.{n}"] for n in _FFN}}
    return {"embed": plain["embed"], "groups": {"blk0": blk},
            "final_norm": {"scale": plain["final_norm"]},
            "lm_head": plain["lm_head"]}


def from_program(tree: Mapping) -> Dict[str, jax.Array]:
    """Inverse of :func:`to_program`, for any tree of that structure (the
    parameters, Adam's moments, the gradients)."""
    blk = tree["groups"]["blk0"]
    out = {"embed": tree["embed"], "layers.ln1": blk["ln1"]["scale"]}
    out.update({f"layers.{n}": v for n, v in blk["attn"].items()})
    out["layers.ln2"] = blk["ln2"]["scale"]
    out.update({f"layers.{n}": v for n, v in blk["ffn"].items()})
    out["final_norm"] = tree["final_norm"]["scale"]
    out["lm_head"] = tree["lm_head"]
    return out
