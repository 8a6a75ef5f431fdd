"""Pallas selective-scan kernel (Mamba-1, as in Jamba's mamba blocks).

TPU mapping: grid (B, Di/dblk, T/chunk), time innermost; the (dblk, N) SSM
state lives in VMEM scratch across time chunks (no HBM round-trips — the
hardware-aware-scan idea from the Mamba paper mapped to TPU's memory
hierarchy). The channel dim is blocked (dblk) so each program's working set
(chunk x dblk inputs + dblk x N state) fits VMEM; dblk should be a multiple
of 128 for lane alignment on real hardware."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Accum = jnp.float32


def _kernel(dt_ref, a_ref, b_ref, c_ref, x_ref, y_ref, hT_ref, h_ref,
            *, chunk: int, n_chunks: int):
    t_id = pl.program_id(2)

    @pl.when(t_id == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    A = a_ref[...].astype(Accum)                  # (dblk, N)

    def step(i, _):
        dt = dt_ref[0, i].astype(Accum)           # (dblk,)
        bm = b_ref[0, i].astype(Accum)            # (N,)
        cm = c_ref[0, i].astype(Accum)            # (N,)
        x = x_ref[0, i].astype(Accum)             # (dblk,)
        h = h_ref[...]                            # (dblk, N)
        dA = jnp.exp(dt[:, None] * A)
        h = dA * h + (dt * x)[:, None] * bm[None, :]
        h_ref[...] = h
        y_ref[0, i] = (h * cm[None, :]).sum(axis=-1).astype(y_ref.dtype)
        return ()

    jax.lax.fori_loop(0, chunk, step, ())

    @pl.when(t_id == n_chunks - 1)
    def _flush():
        hT_ref[0] = h_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "dblk", "interpret"))
def mamba_scan(dt, A, Bm, Cm, x, *, chunk: int = 128, dblk: int = 256,
               interpret: bool):
    """dt,x: (B,T,Di); A: (Di,N); Bm,Cm: (B,T,N).
    Returns y (B,T,Di) fp32, hT (B,Di,N) fp32."""
    B, T, Di = dt.shape
    N = A.shape[1]
    chunk = min(chunk, T)
    dblk = min(dblk, Di)
    assert T % chunk == 0 and Di % dblk == 0, (T, chunk, Di, dblk)
    n_chunks = T // chunk
    n_dblk = Di // dblk

    y, hT = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, n_chunks=n_chunks),
        grid=(B, n_dblk, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, dblk), lambda b, d, t: (b, t, d)),
            pl.BlockSpec((dblk, N), lambda b, d, t: (d, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, d, t: (b, t, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, d, t: (b, t, 0)),
            pl.BlockSpec((1, chunk, dblk), lambda b, d, t: (b, t, d)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, dblk), lambda b, d, t: (b, t, d)),
            pl.BlockSpec((1, dblk, N), lambda b, d, t: (b, d, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, Di), Accum),
            jax.ShapeDtypeStruct((B, Di, N), Accum),
        ],
        scratch_shapes=[pltpu.VMEM((dblk, N), Accum)],
        interpret=interpret,
    )(dt, A, Bm, Cm, x)
    return y, hT
