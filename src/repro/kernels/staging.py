"""Pallas staging kernels for the PiP-MColl collective data paths.

The paper's PiP processes write received fragments straight into the root's
destination buffer (zero-copy shared memory). The TPU analogues are fused
VMEM-tiled copies:

  shift_blocks — paper step 6: rotate the offset-ordered gather buffer into
                 rank order (jnp.roll equivalent). The shift is a runtime
                 value (node index), delivered via scalar prefetch so the
                 BlockSpec index map stays static.
  pack_blocks  — multi-object send staging: gather the rows each lane ships
                 (index list via scalar prefetch).

Both are bandwidth-trivial but latency-critical in the small-message regime
the paper targets — fusing them avoids an extra HBM round-trip between the
collective permute and the consumer."""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _shift_kernel(shift_ref, src_ref, o_ref, *, n_blocks: int):
    # out block i <- src block (i - shift) mod N, resolved via the index map
    o_ref[...] = src_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def shift_blocks(v, shift, *, interpret: bool):
    """v: (N, m) (block-major gather buffer); returns roll(v, shift, 0)."""
    N = v.shape[0]
    m = math.prod(v.shape[1:]) or 1
    flat = v.reshape(N, m)
    sh = jnp.asarray(shift, jnp.int32).reshape(1)
    out = pl.pallas_call(
        functools.partial(_shift_kernel, n_blocks=N),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(N,),
            in_specs=[pl.BlockSpec((1, m),
                                   lambda i, sh: ((i - sh[0]) % N, 0))],
            out_specs=pl.BlockSpec((1, m), lambda i, sh: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((N, m), flat.dtype),
        interpret=interpret,
    )(sh, flat)
    return out.reshape(v.shape)


def _pack_kernel(idx_ref, src_ref, o_ref):
    o_ref[...] = src_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def pack_blocks(src, idx, *, interpret: bool):
    """src: (N, m); idx: (K,) int32 — returns src[idx] as a fused gather."""
    N = src.shape[0]
    m = math.prod(src.shape[1:]) or 1
    flat = src.reshape(N, m)
    K = idx.shape[0]
    out = pl.pallas_call(
        _pack_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(K,),
            in_specs=[pl.BlockSpec((1, m), lambda i, idx: (idx[i], 0))],
            out_specs=pl.BlockSpec((1, m), lambda i, idx: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((K, m), flat.dtype),
        interpret=interpret,
    )(jnp.asarray(idx, jnp.int32), flat)
    return out.reshape((K,) + src.shape[1:])
