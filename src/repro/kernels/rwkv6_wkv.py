"""Pallas WKV6 kernel (RWKV-6 Finch recurrence) — chunked over time with the
per-head (hd, hd) state held in VMEM scratch across chunks.

TPU mapping: grid (B, H, T/chunk); the time-chunk axis is innermost
(sequential), so state S never round-trips HBM between chunks — the paper's
"keep staging in shared memory" idea applied to recurrent state. Within a
chunk a fori_loop runs the exact recurrence; chunk length trades VMEM
footprint (4 x chunk x hd inputs) against grid overhead."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Accum = jnp.float32


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sT_ref, s_ref,
            *, chunk: int, n_chunks: int):
    t_id = pl.program_id(2)

    @pl.when(t_id == 0)
    def _init():
        s_ref[...] = s0_ref[0, 0].astype(Accum)

    u = u_ref[0].astype(Accum)                    # (hd,)

    def step(i, _):
        r = r_ref[0, i, 0].astype(Accum)          # (hd,)
        k = k_ref[0, i, 0].astype(Accum)
        v = v_ref[0, i, 0].astype(Accum)
        w = w_ref[0, i, 0].astype(Accum)
        S = s_ref[...]                            # (hd, hd)
        kv = k[:, None] * v[None, :]
        y = ((S + u[:, None] * kv) * r[:, None]).sum(axis=0)
        y_ref[0, i, 0] = y.astype(y_ref.dtype)
        s_ref[...] = w[:, None] * S + kv
        return ()

    jax.lax.fori_loop(0, chunk, step, ())

    @pl.when(t_id == n_chunks - 1)
    def _flush():
        sT_ref[0, 0] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_wkv(r, k, v, w, u, s0, *, chunk: int = 128, interpret: bool):
    """r,k,v,w: (B,T,H,hd) (w = decay in (0,1), fp32-safe); u: (H,hd);
    s0: (B,H,hd,hd). Returns y (B,T,H,hd) fp32, sT (B,H,hd,hd) fp32."""
    B, T, H, hd = r.shape
    chunk = min(chunk, T)
    assert T % chunk == 0, (T, chunk)
    n_chunks = T // chunk

    y, sT = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, n_chunks=n_chunks),
        grid=(B, H, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, 1, hd), lambda b, h, t: (b, t, h, 0)),
            pl.BlockSpec((1, chunk, 1, hd), lambda b, h, t: (b, t, h, 0)),
            pl.BlockSpec((1, chunk, 1, hd), lambda b, h, t: (b, t, h, 0)),
            pl.BlockSpec((1, chunk, 1, hd), lambda b, h, t: (b, t, h, 0)),
            pl.BlockSpec((1, hd), lambda b, h, t: (h, 0)),
            pl.BlockSpec((1, 1, hd, hd), lambda b, h, t: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, hd), lambda b, h, t: (b, t, h, 0)),
            pl.BlockSpec((1, 1, hd, hd), lambda b, h, t: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, H, hd), Accum),
            jax.ShapeDtypeStruct((B, H, hd, hd), Accum),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), Accum)],
        interpret=interpret,
    )(r, k, v, w, u, s0)
    return y, sT
