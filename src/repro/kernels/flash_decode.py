"""Pallas flash-decode kernel: one-token GQA attention against a long KV
cache with online softmax and VMEM accumulators.

TPU mapping: grid (B, KV, S/chunk) with the sequence-chunk axis innermost
(sequential on TPU), so the (G, hd) accumulator lives in VMEM scratch across
chunks and K/V stream HBM->VMEM exactly once. `chunk` is the BlockSpec-level
tuning knob (VMEM footprint = 2*chunk*hd*2B + (G,hd) accumulators). The
valid-length index arrives via scalar prefetch so block indexing stays
static. Validated in interpret mode against ref.flash_decode (this container
cannot execute compiled TPU kernels)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Accum = jnp.float32
NEG_INF = -1e30


def _kernel(idx_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, chunk: int, n_chunks: int, scale: float):
    s_id = pl.program_id(2)

    @pl.when(s_id == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(Accum)                 # (G, hd)
    k = k_ref[0, :, 0].astype(Accum)              # (chunk, hd)
    v = v_ref[0, :, 0].astype(Accum)              # (chunk, hd)
    cur = idx_ref[0]

    pos = s_id * chunk + jax.lax.iota(jnp.int32, chunk)
    s = jnp.dot(q, k.T, preferred_element_type=Accum) * scale  # (G, chunk)
    s = jnp.where((pos < cur)[None, :], s, NEG_INF)

    m_prev = m_ref[...]                            # (G, 1)
    m_new = jnp.maximum(m_prev[:, 0], s.max(axis=-1))[:, None]
    p = jnp.exp(s - m_new)                         # (G, chunk)
    # fully-masked chunks contribute nothing (exp(NEG_INF - m) ~ 0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)[:, None]
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v, preferred_element_type=Accum)
    m_ref[...] = m_new

    @pl.when(s_id == n_chunks - 1)
    def _flush():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def flash_decode(q, k, v, cur_index, *, chunk: int = 512,
                 interpret: bool):
    """q: (B,1,H,hd); k,v: (B,S,KV,hd); positions < cur_index are valid.
    Returns (B,1,H*hd) fp32."""
    B, _, H, hd = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    n_chunks = S // chunk
    qg = q.reshape(B, KV, G, hd)
    idx = jnp.asarray(cur_index, jnp.int32).reshape(1)

    out = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, n_chunks=n_chunks,
                          scale=1.0 / hd ** 0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, KV, n_chunks),
            in_specs=[
                pl.BlockSpec((1, 1, G, hd),
                             lambda b, kv, s, idx: (b, kv, 0, 0)),
                pl.BlockSpec((1, chunk, 1, hd),
                             lambda b, kv, s, idx: (b, s, kv, 0)),
                pl.BlockSpec((1, chunk, 1, hd),
                             lambda b, kv, s, idx: (b, s, kv, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, G, hd),
                                   lambda b, kv, s, idx: (b, kv, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 1), Accum),
                pltpu.VMEM((G, 1), Accum),
                pltpu.VMEM((G, hd), Accum),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), Accum),
        interpret=interpret,
    )(idx, qg, k, v)
    return out.reshape(B, 1, H * hd)
