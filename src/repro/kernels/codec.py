"""Pallas fused codec kernels for the compressed-collective hot path.

The jnp codecs in ``repro.core.compress`` execute the wire path as separate
streaming passes over the hottest bytes in the system: quantize, ship,
dequantize, add — with error feedback adding a decode and a subtract on
top. Each pass is a full HBM round trip. Following the paper's core claim
(eliminating extra copies/passes is what unlocks message rate) and C-Coll's
observation that codec work sits directly on the wire path, this module
fuses them:

  encode + error-feedback   read the f32 payload (and optionally the carried
                            residual) ONCE; emit the wire blocks, the scales
                            AND the updated residual from registers — the
                            intermediate ``decode(encode(x))`` tensor never
                            materializes in HBM. (fp8_sim scales by the
                            slice max, so its payload is read once more, by
                            the reduction ahead of the kernel.)
  decode + reduce           accumulate the ``W`` incoming wire slices into
                            f32 registers directly (the reduction runs over
                            the grid's inner axis into a revisited output
                            block), replacing dequantize-then-``sum(axis=0)``.

Kernels exist for the ``int8_block``, ``int4_block`` (packed two-per-byte)
and ``fp8_sim`` (when the float8 dtype exists) wire forms. Each is
registered here as a :class:`CodecLowering`; ``core.compress`` routes
``Codec.encode_with_feedback`` / ``encode_residual`` / ``decode_reduce``
through the lowering when ``CodecMeta.fused`` advertises it (and the
module-level fused toggle is on — ``compress.jnp_reference_paths()`` is the
A/B escape hatch conformance uses).

Every kernel walks its payload as rows of one ``BLOCK``-element
quantization block each, a tile of up to 512 rows per grid step; the
per-block max is a row reduction and the scales leave as an ``(R, 1)``
vector block. Backend dispatch follows ``kernels/ops.py``: compiled Pallas
on TPU, interpreted on the CPU backend — CPU CI runs the same kernel bodies
through the interpreter, so the fused paths are conformance-tested
everywhere.

:func:`memory_traffic` is the analytic per-stage HBM byte count (jnp passes
vs fused passes) the codec-kernel microbench and the cost model's
fewer-passes pricing are derived from.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.core.compress import BLOCK
from repro.kernels.ops import interpret as _interpret

_FP8_MAX = 448.0  # e4m3 finite max (matches compress.Fp8SimCodec)
_HAVE_FP8 = hasattr(jnp, "float8_e4m3fn")
_HALF = BLOCK // 2

# Each grid step handles a tile of R quantization blocks laid out as rows
# of an (N, BLOCK) array: all N rows when N <= 512 (a block that spans the
# array is always legal), else 512 rows, a multiple of 32 so int8/uint8
# tiles fill whole (32, 128) vregs. The last tile may run past N: rows are
# independent and Pallas drops the writes of the rows past the edge. 512
# rows keep a step's double-buffered tiles at a few MiB of VMEM.
_MAX_ROWS = 512


def _tile_rows(n_rows: int) -> int:
    return min(n_rows, _MAX_ROWS)


def _pad_axis(a, axis: int, n: int):
    pad = [(0, 0)] * a.ndim
    pad[axis] = (0, n - a.shape[axis])
    return jnp.pad(a, pad)


def _as_rows(a, L: int):
    """(S, L) -> (S * nb, BLOCK) f32, zero-padded: one block per row."""
    a = jnp.asarray(a).astype(jnp.float32)
    nb = -(-L // BLOCK)
    return _pad_axis(a, 1, nb * BLOCK).reshape(a.shape[0] * nb, BLOCK), nb


def _encode_call(quant, rows, wire_dtype, wire_cols: int, interpret: bool,
                 scale_rows=None):
    """One fused pass over (N, BLOCK) rows: read the payload (plus the
    carried residual when ``rows`` holds two arrays) once and write
    wire ``(N, wire_cols)``, scale ``(N, 1)`` and residual ``(N, BLOCK)``.

    ``quant(c, scale)`` maps a corrected (R, BLOCK) tile to ``(wire,
    scale, dequantized)``; ``scale`` is the (R, 1) per-row scale when the
    caller supplies ``scale_rows``, else None (the kernel derives it as a
    row reduction and writes it out)."""
    N = rows[0].shape[0]
    R = _tile_rows(N)
    row = pl.BlockSpec((R, BLOCK), lambda i: (i, 0))
    col = pl.BlockSpec((R, 1), lambda i: (i, 0))
    given = scale_rows is not None
    args = list(rows) + ([scale_rows] if given else [])
    n_x = len(rows)

    def kernel(*refs):
        c = refs[0][...]
        for r in refs[1:n_x]:
            c = c + r[...]
        wire, scale, deq = quant(c, refs[n_x][...] if given else None)
        outs = refs[len(args):]
        outs[0][...] = wire
        if not given:
            outs[1][...] = scale
        outs[-1][...] = c - deq

    out_specs = [pl.BlockSpec((R, wire_cols), lambda i: (i, 0))]
    out_shape = [jax.ShapeDtypeStruct((N, wire_cols), wire_dtype)]
    if not given:
        out_specs.append(col)
        out_shape.append(jax.ShapeDtypeStruct((N, 1), jnp.float32))
    out_specs.append(row)
    out_shape.append(jax.ShapeDtypeStruct((N, BLOCK), jnp.float32))
    return pl.pallas_call(
        kernel, grid=(pl.cdiv(N, R),),
        in_specs=[row] * n_x + ([col] if given else []),
        out_specs=out_specs, out_shape=out_shape, interpret=interpret,
    )(*args)


def _decode_call(dequant, wire3, scale3, interpret: bool):
    """Fused decode + sum over the leading wire-peer axis: wire
    ``(W, nb, C)`` and per-row scales ``(W, nb, 1)`` accumulate into an
    f32 ``(nb, BLOCK)`` output tile revisited along the inner grid axis."""
    W, nb, C = wire3.shape
    R = _tile_rows(nb)

    def kernel(q_ref, s_ref, o_ref):
        @pl.when(pl.program_id(1) == 0)
        def _init():
            o_ref[...] = jnp.zeros_like(o_ref)

        o_ref[...] += dequant(q_ref[0]) * s_ref[0]

    out = pl.pallas_call(
        kernel, grid=(pl.cdiv(nb, R), W),
        in_specs=[pl.BlockSpec((1, R, C), lambda b, w: (w, b, 0)),
                  pl.BlockSpec((1, R, 1), lambda b, w: (w, b, 0))],
        out_specs=pl.BlockSpec((R, BLOCK), lambda b, w: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, BLOCK), jnp.float32),
        interpret=interpret,
    )(wire3, scale3)
    return out.reshape(-1)


def _block_encoder(quant, wire_dtype, wire_cols: int):
    """Jitted ``encode(x2d[, err], *, interpret) -> (comp, residual)`` for
    a per-block codec: with ``err`` it is encode + error feedback, without
    it encode + round-trip residual."""

    @functools.partial(jax.jit, static_argnames=("interpret",))
    def encode(*xs, interpret: bool):
        S, L = xs[0].shape
        rows = [_as_rows(a, L)[0] for a in xs]
        nb = rows[0].shape[0] // S
        q, scale, res = _encode_call(quant, rows, wire_dtype, wire_cols,
                                     interpret)
        return ({"q": q.reshape(S, nb, wire_cols),
                 "scale": scale.reshape(S, nb)},
                res.reshape(S, nb * BLOCK)[:, :L])

    return encode


def _block_decoder(dequant):
    """Jitted ``decode_reduce(comp, length, *, interpret) -> (length,)``
    for a per-block wire form ``{"q": (W, nb, C), "scale": (W, nb)}``."""

    @functools.partial(jax.jit, static_argnames=("length", "interpret"))
    def decode_reduce(comp, length: int, *, interpret: bool):
        scale = comp["scale"]
        return _decode_call(dequant, comp["q"], scale[..., None],
                            interpret)[:length]

    return decode_reduce


# ---------------------------------------------------------------------------
# int8_block: per-256-block int8 + fp32 scale
# ---------------------------------------------------------------------------


def _i8_quant(c, _):
    """Same arithmetic as the jnp codec: scale = blockmax/127,
    round-to-nearest, clamped divisor."""
    scale = jnp.max(jnp.abs(c), axis=1, keepdims=True) / 127.0
    q = jnp.clip(jnp.round(c / jnp.maximum(scale, 1e-12)), -127, 127)
    return q.astype(jnp.int8), scale, q * scale


int8_encode = _block_encoder(_i8_quant, jnp.int8, BLOCK)
int8_decode_reduce = _block_decoder(lambda q: q.astype(jnp.float32))


# ---------------------------------------------------------------------------
# int4_block: packed two-per-byte wire form, per-256-block fp32 scale
#
# Nibble pairs (+8 bias, even element in the low nibble) are formed and
# split with 0/1/16 selection matmuls rather than a lane-interleaving
# reshape, which Mosaic cannot lower. Every operand is a small integer, so
# the bf16 products and f32 sums are exact.
# ---------------------------------------------------------------------------


def _pack_matrix():
    """(BLOCK, HALF): lane 2j -> column j with weight 1, lane 2j+1 with 16."""
    i = lax.broadcasted_iota(jnp.int32, (BLOCK, _HALF), 0)
    j = lax.broadcasted_iota(jnp.int32, (BLOCK, _HALF), 1)
    m = jnp.where(i == 2 * j, 1.0, jnp.where(i == 2 * j + 1, 16.0, 0.0))
    return m.astype(jnp.bfloat16)


def _unpack_matrix(parity: int):
    """(HALF, BLOCK): column j -> lane 2j + parity."""
    j = lax.broadcasted_iota(jnp.int32, (_HALF, BLOCK), 0)
    k = lax.broadcasted_iota(jnp.int32, (_HALF, BLOCK), 1)
    return (k == 2 * j + parity).astype(jnp.bfloat16)


def _i4_quant(c, _):
    """Quantize to [-7, 7] against blockmax/7 and pack nibble pairs —
    mirrors Int4BlockCodec.encode."""
    scale = jnp.max(jnp.abs(c), axis=1, keepdims=True) / 7.0
    q = jnp.clip(jnp.round(c / jnp.maximum(scale, 1e-12)), -7, 7)
    packed = jnp.dot((q + 8.0).astype(jnp.bfloat16), _pack_matrix(),
                     preferred_element_type=jnp.float32)
    return packed.astype(jnp.int32).astype(jnp.uint8), scale, q * scale


def _i4_dequant(b):
    b = b.astype(jnp.int32)
    lo = ((b & 0xF) - 8).astype(jnp.bfloat16)
    hi = ((b >> 4) - 8).astype(jnp.bfloat16)
    return (jnp.dot(lo, _unpack_matrix(0), preferred_element_type=jnp.float32)
            + jnp.dot(hi, _unpack_matrix(1),
                      preferred_element_type=jnp.float32))


int4_encode = _block_encoder(_i4_quant, jnp.uint8, _HALF)
int4_decode_reduce = _block_decoder(_i4_dequant)


# ---------------------------------------------------------------------------
# fp8_sim: e4m3 cast against a per-slice scale. The slice amax is one
# reduction pass ahead of the kernel, a second read of the payload (a
# slice can outgrow VMEM, so the kernel cannot hold it for one pass); the
# kernel then runs the same row tiles as the block codecs with each row's
# slice scale as an input.
# ---------------------------------------------------------------------------


def _fp8_quant(c, scale):
    q = jnp.clip(c / scale, -_FP8_MAX, _FP8_MAX)
    f8 = q.astype(jnp.float8_e4m3fn)
    return (lax.bitcast_convert_type(f8, jnp.uint8), scale,
            f8.astype(jnp.float32) * scale)


def _fp8_dequant(q):
    return lax.bitcast_convert_type(q, jnp.float8_e4m3fn).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fp8_encode(*xs, interpret: bool):
    S, L = xs[0].shape
    rows, nb = zip(*(_as_rows(a, L) for a in xs))
    c = rows[0] if len(rows) == 1 else rows[0] + rows[1]
    amax = jnp.max(jnp.abs(c).reshape(S, -1), axis=1)
    scale = jnp.maximum(amax / _FP8_MAX, 1e-30)
    q, res = _encode_call(_fp8_quant, list(rows), jnp.uint8, BLOCK,
                          interpret,
                          scale_rows=jnp.repeat(scale, nb[0])[:, None])
    return ({"q": q.reshape(S, nb[0] * BLOCK)[:, :L], "scale": scale},
            res.reshape(S, nb[0] * BLOCK)[:, :L])


@functools.partial(jax.jit, static_argnames=("length", "interpret"))
def fp8_decode_reduce(comp, length: int, *, interpret: bool):
    q, scale = comp["q"], comp["scale"]
    W, L = q.shape
    nb = -(-L // BLOCK)
    q3 = _pad_axis(q, 1, nb * BLOCK).reshape(W, nb, BLOCK)
    s3 = jnp.broadcast_to(scale[:, None, None], (W, nb, 1))
    return _decode_call(_fp8_dequant, q3, s3, interpret)[:length]


# ---------------------------------------------------------------------------
# per-codec lowering registry (what CodecMeta.fused points at)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CodecLowering:
    """The fused entry points for one codec's wire form.

    ``encode(x2d[, err], *, interpret)`` and ``decode(comp, length, *,
    interpret)`` are the jitted kernels; the methods bind ``interpret``
    from the backend (``kernels.ops.interpret``):

    encode_feedback(x2d, err) -> (comp, new_err)   one pass over x + err
    encode_residual(x2d)      -> (comp, residual)  one pass over x
    decode_reduce(comp, L)    -> (L,) f32          one pass over the wire

    ``amax_pass``: the codec scales by a per-slice max, so both encodes
    read their payload once more, in a reduction ahead of the kernel.
    """

    name: str
    encode: Callable
    decode: Callable
    amax_pass: bool = False

    def encode_feedback(self, x2d, err):
        return self.encode(x2d, err, interpret=_interpret())

    def encode_residual(self, x2d):
        return self.encode(x2d, interpret=_interpret())

    def decode_reduce(self, comp, length: int):
        return self.decode(comp, length, interpret=_interpret())


LOWERINGS: Dict[str, CodecLowering] = {}


def _register(lw: CodecLowering) -> CodecLowering:
    LOWERINGS[lw.name] = lw
    return lw


_register(CodecLowering("int8_block", int8_encode, int8_decode_reduce))
_register(CodecLowering("int4_block", int4_encode, int4_decode_reduce))
if _HAVE_FP8:
    _register(CodecLowering("fp8_sim", fp8_encode, fp8_decode_reduce,
                            amax_pass=True))


def lowering(name: str) -> Optional[CodecLowering]:
    """The registered fused lowering for one codec name (None = jnp only)."""
    return LOWERINGS.get(name)


def fused_codec_names() -> Tuple[str, ...]:
    return tuple(sorted(LOWERINGS))


# ---------------------------------------------------------------------------
# analytic memory traffic: jnp passes vs fused passes (the numbers behind
# the cost model's fewer-passes pricing and the codec-kernel microbench)
# ---------------------------------------------------------------------------


def memory_traffic(wire_bytes_per_elem: float, n_elems: int,
                   W: int = 8, amax_pass: bool = False
                   ) -> Dict[str, Dict[str, float]]:
    """HBM bytes moved per stage for ``n_elems`` f32 payload elements.

    jnp encode+feedback: add (r8 w4), encode (r4 w b), decode for the
    residual (r b w4), subtract (r8 w4) — every intermediate round-trips
    HBM. Fused: read x + err once (r8), write wire + residual (w b+4).
    With ``amax_pass`` (a per-slice scale, :attr:`CodecLowering.amax_pass`)
    both read the payload once more for its max: jnp the corrected sum it
    wrote (r4), the fused path x + err, added inside the reduction (r8).

    jnp decode+reduce over ``W`` wire slices: dequantize (r b w4) then
    ``sum(axis=0)`` (r4 w 4/W) per wire element. Fused: read the wire
    slices once (r b), accumulate in registers, write f32 once (w 4/W).
    """
    b = float(wire_bytes_per_elem)
    n = float(n_elems)
    return {
        "encode_feedback": {
            "jnp_bytes": n * (8 + 4 + 4 + b + b + 4 + 8 + 4
                              + (4 if amax_pass else 0)),
            "fused_bytes": n * (8 + b + 4 + (8 if amax_pass else 0)),
        },
        "decode_reduce": {
            "jnp_bytes": n * (b + 4 + 4 + 4.0 / W),
            "fused_bytes": n * (b + 4.0 / W),
        },
    }
