"""Error-bounded compression codecs for collective payloads.

The paper's companion work (C-Coll: "An Optimized Error-controlled MPI
Collective Framework Integrated with Lossy Compression", Huang et al. 2023)
shows the axis complementary to multi-object scheduling: integrate
error-bounded lossy compression *inside* the collective algorithms, so what
crosses the slow (inter-node) links shrinks by the codec's wire ratio while
the end-to-end error stays under a stated bound.

This module is the codec side of that subsystem:

  * a **registry** of codecs (:func:`codec`, :func:`codecs`,
    :func:`register`), each exposing ``encode``/``decode`` over slice
    batches, **error-feedback** helpers, and :class:`CodecMeta` —
    wire ratio, flop cost, and a *stated relative-error bound* the
    selection subsystem (``core.autotune``) checks against the caller's
    ``error_budget`` (``error_budget=0.0`` admits only lossless plans);
  * the **compressed execution** in ``core.mcoll`` encodes with these
    codecs before the slow ``node`` axis and decodes after;
  * the **cost model** (``core.costmodel.plan_cost``) prices a compressed
    plan as ``(C + B/ratio·β)·rounds + codec_flops``.

Codecs (stated elementwise round-trip bound, relative to ``max|slice|``):

  ===========  =========  ============  =====================================
  name         ratio      error bound   mechanism
  ===========  =========  ============  =====================================
  none         1.0x       0.0           identity (lossless)
  int8_block   ~3.9x      0.5/127       int8 blocks + per-256-block fp32 scale
  int4_block   ~7.8x      0.5/7         int4 nibble pairs packed two-per-byte
                                        + per-256-block fp32 scale
  fp8_sim      ~4.0x      2^-4          e4m3 cast against a per-slice scale
  topk         ~8.0x      1.0           keep the top 1/16 by magnitude
  zlib_sim     ~2x (meas) 0.0 (int)     bit-width packing: per-slice int32
                                        base + uint16 offsets (lossless for
                                        integer payloads whose per-slice
                                        range fits 16 bits — token ids,
                                        expert indices); wire bytes are
                                        *measured* by a byte-entropy /
                                        run-length stage, not assumed
  ===========  =========  ============  =====================================

Codecs whose :class:`CodecMeta` sets ``fused=True`` additionally register
Pallas lowerings in ``repro.kernels.codec`` that fuse encode+error-feedback
into one memory pass (two for fp8_sim, whose slice max is read first) and
decode+reduce into another;
:meth:`Codec.encode_with_feedback` / :meth:`Codec.encode_residual` /
:meth:`Codec.decode_reduce` route through them unless
:func:`jnp_reference_paths` disables fusion (the conformance A/B switch).
On non-TPU backends the kernels run in interpret mode, so CPU CI exercises
the same kernel bodies.

Encode operates on ``(S, L)`` float32 slice batches (``S`` slices headed for
``S`` wire peers) and returns a dict of arrays with leading dim ``S`` — the
wire form. Every leaf is a plain array, so ``lax.all_to_all`` /
``lax.all_gather`` over the wire axis apply leafwise (``jax.tree.map``).
``decode(comp, L)`` inverts to ``(S, L)`` float32.

The int8 tree-level helpers (:func:`quantize` / :func:`compress_tree` /
...) are the original ``optim.compress`` API, now owned here;
``repro.optim.compress`` re-exports them.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

#: quantization block length for the int8 block codec (elements per scale)
BLOCK = 256

#: density kept by the ``topk`` codec (fraction of elements per slice)
TOPK_DENSITY = 1.0 / 16.0

NONE = "none"


# ---------------------------------------------------------------------------
# codec metadata + base class
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CodecMeta:
    """Selection-facing metadata for one codec.

    wire_ratio:     fp32 payload bytes / wire bytes (>1 = compression); the
                    cost model divides the wire beta by this.
    flops_per_elem: modeled encode+decode work per element (elementwise
                    passes; priced against ``NetParams.flop_rate``).
    error_bound:    stated elementwise round-trip bound
                    ``max|decode(encode(x)) - x| <= error_bound * max|x|``
                    per slice. 0.0 means lossless. The selector admits a
                    codec only when ``error_bound <= error_budget``.
    integer_only:   the codec's domain is integer payloads (its wire form
                    exploits integer structure and its lossless claim holds
                    only there). Integer-only codecs are never admitted for
                    float payloads or reducing collectives — see
                    :func:`admissible`.
    fused:          the codec registers Pallas fused lowerings
                    (encode+error-feedback and decode+reduce in one memory
                    pass each, plus a slice-max read for a per-slice
                    scale) in ``repro.kernels.codec``; the hot-path
                    methods route through them while :func:`fused_enabled`.
    fused_flops_per_elem: modeled per-element work of the *fused* path —
                    fewer memory passes than ``flops_per_elem`` prices
                    (the codec cost is ~HBM-bound streaming, so fewer
                    passes is directly fewer modeled "flops"). ``None``
                    falls back to ``flops_per_elem``. The cost model reads
                    :func:`effective_flops_per_elem`, so autotuned
                    crossovers shift when fusion is on.
    """

    name: str
    wire_ratio: float
    flops_per_elem: float
    error_bound: float
    integer_only: bool = False
    fused: bool = False
    fused_flops_per_elem: Optional[float] = None

    @property
    def lossless(self) -> bool:
        return self.error_bound == 0.0


# ---------------------------------------------------------------------------
# fused-lowering toggle (the conformance A/B switch)
# ---------------------------------------------------------------------------

_FUSED_ENABLED = True


def fused_enabled() -> bool:
    """Whether fused Pallas lowerings are routed (module-level switch)."""
    return _FUSED_ENABLED


def set_fused(enabled: bool) -> bool:
    """Set the fused-lowering switch; returns the previous value."""
    global _FUSED_ENABLED
    prev = _FUSED_ENABLED
    _FUSED_ENABLED = bool(enabled)
    return prev


@contextlib.contextmanager
def jnp_reference_paths():
    """Context manager forcing the pure-jnp reference paths (fusion off).

    The conformance suite runs every fused codec A/B under this to assert
    the kernel paths match the jnp paths; the runtime's plan caches key on
    :func:`fused_enabled` so the two variants compile separately."""
    prev = set_fused(False)
    try:
        yield
    finally:
        set_fused(prev)


class Codec:
    """Base codec: subclasses set ``meta`` and implement encode/decode.

    ``encode(x2d)``: ``(S, L)`` float32 -> dict of arrays, leading dim S.
    ``decode(comp, length)``: inverse, -> ``(S, length)`` float32.
    """

    meta: CodecMeta

    def encode(self, x2d):
        raise NotImplementedError

    def decode(self, comp, length: int):
        raise NotImplementedError

    # -- fused lowerings ----------------------------------------------------

    def _lowering(self):
        """The registered fused Pallas lowering, or None (jnp path)."""
        if not (self.meta.fused and _FUSED_ENABLED):
            return None
        from repro.kernels import codec as _kernels  # lazy: no import cycle
        return _kernels.lowering(self.meta.name)

    # -- error feedback -----------------------------------------------------

    def encode_with_feedback(self, x2d, err):
        """Encode ``x2d + err``; return (wire form, new feedback state).

        Error feedback (Karimireddy et al. 2019): the round-trip residual is
        carried into the next call, so the *accumulated* signal tracks the
        true accumulated signal to within one step's residual — lossy
        gradient compression keeps converging.

        Fused codecs execute this as ONE memory pass (read payload +
        carried residual, emit wire form + new residual from registers);
        the jnp path below materializes the decode round trip.
        """
        lw = self._lowering()
        if lw is not None:
            return lw.encode_feedback(jnp.asarray(x2d).astype(jnp.float32),
                                      err)
        corrected = x2d.astype(jnp.float32) + err
        comp = self.encode(corrected)
        return comp, corrected - self.decode(comp, x2d.shape[-1])

    def encode_residual(self, x2d):
        """Encode ``x2d``; return (wire form, round-trip residual).

        The residual-producing encode on the compressed-collective hot path
        (``core.mcoll``): fused codecs emit wire blocks and the residual in
        one pass, never materializing ``decode(encode(x))``."""
        lw = self._lowering()
        if lw is not None:
            return lw.encode_residual(jnp.asarray(x2d).astype(jnp.float32))
        x2d = jnp.asarray(x2d).astype(jnp.float32)
        comp = self.encode(x2d)
        return comp, x2d - self.decode(comp, x2d.shape[-1])

    def decode_reduce(self, comp, length: int):
        """Decode the ``(W, ...)`` wire form and sum over the peer axis.

        Fused codecs accumulate the incoming wire slices into f32 registers
        directly (one pass over the wire bytes) instead of
        dequantize-then-``sum(axis=0)``."""
        lw = self._lowering()
        if lw is not None:
            return lw.decode_reduce(comp, length)
        return self.decode(comp, length).sum(axis=0)

    # -- observability ------------------------------------------------------

    def wire_bytes(self, comp) -> int:
        """Actual bytes of the wire form (sanity check vs meta.wire_ratio)."""
        return sum(int(a.size) * jnp.dtype(a.dtype).itemsize
                   for a in jax.tree.leaves(comp))

    def achieved_ratio(self, x2d) -> float:
        """Measured compression ratio on one payload: float32 payload bytes
        over actual wire bytes of ``encode(x2d)`` (>= 1 means the codec
        shrinks the wire). Runs an encode, so callers sample it — the
        telemetry EF probe and the benchmark compression section — rather
        than calling it per collective."""
        x2d = jnp.asarray(x2d, jnp.float32)
        return float(x2d.size * 4.0) / max(1, self.wire_bytes(
            self.encode(x2d)))


# ---------------------------------------------------------------------------
# int8 block codec (the original optim.compress math, generalized)
# ---------------------------------------------------------------------------


class Int8BlockCodec(Codec):
    """Per-block int8 quantization: 256-element blocks, one fp32 scale each.

    Round-to-nearest against ``blockmax/127`` bounds the elementwise error
    by ``0.5 * blockmax/127`` — stated bound 0.5/127 relative to the slice
    max (block max <= slice max). Wire: 1 byte/elem + 4 bytes per block
    = 3.94x vs fp32. All-zero blocks get scale 0 (the divisor is clamped,
    so q is exactly 0 — no NaNs)."""

    meta = CodecMeta("int8_block", wire_ratio=BLOCK * 4 / (BLOCK + 4.0),
                     flops_per_elem=3.0, error_bound=0.5 / 127.0,
                     fused=True, fused_flops_per_elem=1.5)

    def encode(self, x2d):
        S, L = x2d.shape
        nb = -(-L // BLOCK)
        padded = jnp.pad(x2d.astype(jnp.float32), ((0, 0), (0, nb * BLOCK - L)))
        blocks = padded.reshape(S, nb, BLOCK)
        scale = jnp.max(jnp.abs(blocks), axis=2) / 127.0
        q = jnp.clip(jnp.round(blocks / jnp.maximum(scale[..., None], 1e-12)),
                     -127, 127)
        return {"q": q.astype(jnp.int8), "scale": scale}

    def decode(self, comp, length: int):
        q, scale = comp["q"], comp["scale"]
        S = q.shape[0]
        deq = q.astype(jnp.float32) * scale[..., None]
        return deq.reshape(S, -1)[:, :length]


_INT8 = Int8BlockCodec()


def quantize(x):
    """x: float array -> (int8 blocks, fp32 per-block scales).

    Legacy flat-array face of :class:`Int8BlockCodec` (single
    implementation of the block math; this just adapts shapes)."""
    comp = _INT8.encode(jnp.asarray(x).reshape(1, -1))
    return comp["q"][0], comp["scale"][0]


def dequantize(q, scale, shape):
    n = 1
    for d in shape:
        n *= d
    return _INT8.decode({"q": q[None], "scale": scale[None]},
                        n)[0].reshape(shape)


# ---------------------------------------------------------------------------
# int4 block codec: nibble pairs packed two-per-byte
# ---------------------------------------------------------------------------


class Int4BlockCodec(Codec):
    """Per-block int4 quantization, packed two values per wire byte.

    Same block structure as :class:`Int8BlockCodec` but quantized to
    ``[-7, 7]`` against ``blockmax/7`` and shipped as nibble pairs: each
    wire byte holds two consecutive elements (+8 bias, even element in the
    low nibble) — 0.5 bytes/elem + 4 bytes per block, ~7.8x vs fp32.
    Round-to-nearest bounds the elementwise error by ``0.5 * blockmax/7``,
    so the stated bound is 0.5/7 relative to the slice max. The packing
    layout here is the contract the fused Pallas kernels
    (``kernels/codec.py``) reproduce bit-for-bit."""

    meta = CodecMeta("int4_block", wire_ratio=BLOCK * 4 / (BLOCK / 2 + 4.0),
                     flops_per_elem=4.0, error_bound=0.5 / 7.0,
                     fused=True, fused_flops_per_elem=2.0)

    def encode(self, x2d):
        S, L = x2d.shape
        nb = -(-L // BLOCK)
        padded = jnp.pad(x2d.astype(jnp.float32), ((0, 0), (0, nb * BLOCK - L)))
        blocks = padded.reshape(S, nb, BLOCK)
        scale = jnp.max(jnp.abs(blocks), axis=2) / 7.0
        q = jnp.clip(jnp.round(blocks / jnp.maximum(scale[..., None], 1e-12)),
                     -7, 7)
        pairs = (q.astype(jnp.int32) + 8).reshape(S, nb, BLOCK // 2, 2)
        packed = (pairs[..., 0] | (pairs[..., 1] << 4)).astype(jnp.uint8)
        return {"q": packed, "scale": scale}

    def decode(self, comp, length: int):
        packed, scale = comp["q"], comp["scale"]
        S, nb = scale.shape
        b = packed.astype(jnp.int32)
        lo = (b & 0xF) - 8
        hi = (b >> 4) - 8
        q = jnp.stack([lo, hi], axis=-1).reshape(S, nb, BLOCK)
        deq = q.astype(jnp.float32) * scale[..., None]
        return deq.reshape(S, -1)[:, :length]


# ---------------------------------------------------------------------------
# fp8 (e4m3) cast codec
# ---------------------------------------------------------------------------

_FP8_MAX = 448.0  # e4m3 finite max
_HAVE_FP8 = hasattr(jnp, "float8_e4m3fn")


def _sim_e4m3(x):
    """Mantissa-rounding fallback when the float8 dtype is unavailable:
    3 mantissa bits via frexp/ldexp (matches e4m3 normals' 2^-4 bound)."""
    m, e = jnp.frexp(x)
    return jnp.ldexp(jnp.round(m * 16.0) / 16.0, e)


class Fp8SimCodec(Codec):
    """e4m3 cast against a per-slice scale (``amax/448``).

    Round-to-nearest on a 3-bit mantissa bounds the relative error of every
    normal by 2^-4; scaling to the slice max keeps the whole slice in the
    normal range, so the stated bound is 2^-4 relative to the slice max.
    The wire form carries the fp8 payload bitcast to uint8 (collectives
    move uint8 everywhere) plus one fp32 scale per slice: ~4x vs fp32.

    Without the float8 dtype the frexp/ldexp fallback simulates only the
    *accuracy* (fp32 stays on the wire), so the declared ratio drops to
    1.0 — the selector then never prices savings that don't exist.
    """

    meta = CodecMeta("fp8_sim",
                     wire_ratio=4.0 * (1.0 - 1e-3) if _HAVE_FP8 else 1.0,
                     flops_per_elem=2.0, error_bound=2.0 ** -4,
                     # the fused encode reads its payload twice (the
                     # slice max, then the kernel): 21 B/elem against
                     # 13 for one pass (kernels.codec.memory_traffic),
                     # so the one-pass price 1.0 scales to 1.6
                     fused=_HAVE_FP8, fused_flops_per_elem=1.6)

    def encode(self, x2d):
        x2d = x2d.astype(jnp.float32)
        amax = jnp.max(jnp.abs(x2d), axis=1)
        scale = jnp.maximum(amax / _FP8_MAX, 1e-30)
        q = jnp.clip(x2d / scale[:, None], -_FP8_MAX, _FP8_MAX)
        if _HAVE_FP8:
            wire = lax.bitcast_convert_type(q.astype(jnp.float8_e4m3fn),
                                            jnp.uint8)
        else:
            wire = _sim_e4m3(q)
        return {"q": wire, "scale": scale}

    def decode(self, comp, length: int):
        q, scale = comp["q"], comp["scale"]
        if _HAVE_FP8:
            q = lax.bitcast_convert_type(q, jnp.float8_e4m3fn)
        return q.astype(jnp.float32)[:, :length] * scale[:, None]


# ---------------------------------------------------------------------------
# top-k sparsification codec
# ---------------------------------------------------------------------------


class TopKCodec(Codec):
    """Keep the ``TOPK_DENSITY`` largest-magnitude elements per slice.

    Dropped elements carry their full value as error, and the largest
    dropped magnitude can approach the slice max — the honest stated bound
    is 1.0 (admitted only under a permissive error budget; error feedback
    is what makes repeated top-k converge in gradient paths). Wire: (value
    fp32 + index int32) per kept element = ``1/(2*density)`` vs fp32."""

    meta = CodecMeta("topk", wire_ratio=1.0 / (2.0 * TOPK_DENSITY),
                     flops_per_elem=6.0, error_bound=1.0)

    def encode(self, x2d):
        x2d = x2d.astype(jnp.float32)
        S, L = x2d.shape
        k = max(1, int(math.ceil(L * TOPK_DENSITY)))
        _, idx = lax.top_k(jnp.abs(x2d), k)
        vals = jnp.take_along_axis(x2d, idx, axis=1)
        return {"v": vals, "i": idx.astype(jnp.int32)}

    def decode(self, comp, length: int):
        vals, idx = comp["v"], comp["i"]
        S = vals.shape[0]
        out = jnp.zeros((S, length), jnp.float32)
        return out.at[jnp.arange(S)[:, None], idx].set(vals)


# ---------------------------------------------------------------------------
# identity codec (the lossless plan dimension)
# ---------------------------------------------------------------------------


class NoneCodec(Codec):
    """Identity: the ``codec`` plan dimension's lossless value."""

    meta = CodecMeta(NONE, wire_ratio=1.0, flops_per_elem=0.0,
                     error_bound=0.0)

    def encode(self, x2d):
        return {"x": x2d.astype(jnp.float32)}

    def decode(self, comp, length: int):
        return comp["x"][:, :length]


# ---------------------------------------------------------------------------
# lossless integer bit-width packing (zlib_sim)
# ---------------------------------------------------------------------------


def _entropy_wire_bytes(raw: np.ndarray) -> int:
    """Measured byte estimate for one packed byte stream.

    Two stages a byte-stream compressor actually has, each computed from
    the concrete bytes (nothing assumed): an order-0 entropy coder
    (``n * H / 8`` bytes from the byte histogram) and a run-length coder
    (2 bytes per run: value + length). The estimate is the better of the
    two, never exceeding the raw stream."""
    raw = np.ascontiguousarray(raw, dtype=np.uint8).reshape(-1)
    n = int(raw.size)
    if n == 0:
        return 0
    hist = np.bincount(raw, minlength=256).astype(np.float64)
    p = hist[hist > 0] / n
    entropy_bits = float(-(p * np.log2(p)).sum())
    entropy_bytes = int(math.ceil(n * entropy_bits / 8.0))
    runs = int(1 + np.count_nonzero(raw[1:] != raw[:-1]))
    rle_bytes = 2 * runs
    return max(1, min(n, entropy_bytes, rle_bytes))


class ZlibSimCodec(Codec):
    """Lossless bit-width packing for small-range integer payloads.

    What a byte-stream compressor (zlib) exploits in token/index traffic is
    mostly the narrow value range; this codec captures that win in a fixed
    wire shape JAX can trace: per slice, one int32 ``base`` (the slice min)
    plus 16-bit offsets ``lo = v - base``. Wire: 2 bytes/elem + 4 bytes per
    slice, ~2x vs the 4-byte integer payload.

    Domain contract (why ``integer_only``): the round trip is exact iff
    every slice's value range fits 16 bits (``max - min < 2**16``) — true
    for vocabulary token ids, expert/router indices, and lengths, which are
    exactly the payloads otherwise forced to ``codec="none"``. Shapes are
    static under jit, so the 16-bit width is a declared contract, not a
    measured one; out-of-range offsets wrap (detectably garbage, not
    silently close). Float payloads and reducing collectives (the wire form
    cannot be summed) are excluded by :func:`admissible`.

    Unlike the float codecs, encode keeps integer dtypes as-is (no f32
    cast) and decode returns int32 — the compressed execution casts back to
    the caller's integer dtype, so values above 2**24 survive the trip.

    The wire accounting is *measured*, not assumed: :meth:`wire_bytes`
    runs the packed offsets through :func:`_entropy_wire_bytes` (order-0
    byte entropy vs run-length, whichever is smaller), ``meta.wire_ratio``
    is seeded at registration from a canonical token-id sample through the
    same estimator, and :meth:`refresh_ratio` re-measures it against a
    caller's real payload so the cost model prices observed bytes.
    """

    meta = CodecMeta("zlib_sim", wire_ratio=2.0 * (1.0 - 1e-3),
                     flops_per_elem=2.0, error_bound=0.0, integer_only=True)

    def __init__(self):
        # Seed the declared ratio from a measured sample (quasi-uniform
        # vocabulary token ids — the canonical integer payload) instead of
        # the historical assumed 2x. numpy-only: runs at import time.
        ids = (np.arange(4096, dtype=np.int64) * 2654435761) % 50257
        self.meta = dataclasses.replace(
            type(self).meta,
            wire_ratio=self._measured_ratio_np(ids.astype(np.int32)
                                               .reshape(1, -1)))

    @staticmethod
    def _measured_ratio_np(v2d: np.ndarray) -> float:
        """payload bytes / measured wire bytes for an int32 sample."""
        base = v2d.min(axis=1, keepdims=True)
        lo = (v2d - base).astype(np.uint16)
        wire = _entropy_wire_bytes(lo.view(np.uint8)) + 4 * v2d.shape[0]
        return float(v2d.size * 4.0 / wire)

    def wire_bytes(self, comp) -> int:
        """Measured wire bytes: entropy/run-length estimate on the packed
        offsets plus the 4-byte per-slice bases (overrides the assumed
        leaf-nbytes accounting of the base class)."""
        lo = np.asarray(jax.device_get(comp["lo"])).astype(np.uint16)
        n_slices = int(comp["base"].size)
        return _entropy_wire_bytes(lo.view(np.uint8)) + 4 * n_slices

    def refresh_ratio(self, x2d) -> float:
        """Re-measure ``meta.wire_ratio`` against a concrete sample payload
        and install it on this (registered) instance; returns the ratio."""
        v = np.asarray(jax.device_get(jnp.asarray(x2d))).astype(np.int32)
        if v.ndim == 1:
            v = v.reshape(1, -1)
        ratio = self._measured_ratio_np(v)
        self.meta = dataclasses.replace(self.meta, wire_ratio=ratio)
        return ratio

    def encode(self, x2d):
        v = jnp.asarray(x2d).astype(jnp.int32)
        base = jnp.min(v, axis=1)
        lo = (v - base[:, None]).astype(jnp.uint16)
        return {"lo": lo, "base": base}

    def decode(self, comp, length: int):
        lo, base = comp["lo"], comp["base"]
        return (base[:, None] + lo.astype(jnp.int32))[:, :length]


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, Codec] = {}


def register(c: Codec) -> Codec:
    _REGISTRY[c.meta.name] = c
    return c


register(NoneCodec())
register(_INT8)
register(Int4BlockCodec())
register(Fp8SimCodec())
register(TopKCodec())
register(ZlibSimCodec())


def codecs() -> Tuple[str, ...]:
    """All registered codec names, ``"none"`` first, rest sorted."""
    rest = sorted(n for n in _REGISTRY if n != NONE)
    return (NONE, *rest)


def lossy() -> Tuple[str, ...]:
    """Registered lossy codec names (sorted)."""
    return tuple(n for n in codecs() if not _REGISTRY[n].meta.lossless)


def codec(name: str) -> Codec:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown codec {name!r}; one of {codecs()}") \
            from None


def meta(name: str) -> CodecMeta:
    return codec(name).meta


def fused_codecs() -> Tuple[str, ...]:
    """Registered codec names advertising fused Pallas lowerings."""
    return tuple(n for n in codecs() if _REGISTRY[n].meta.fused)


def effective_flops_per_elem(name: str) -> float:
    """The per-element codec work the cost model should price *right now*:
    the fused figure when the codec advertises a fused lowering and fusion
    is enabled (fewer memory passes), else the jnp figure."""
    m = meta(name)
    if m.fused and _FUSED_ENABLED and m.fused_flops_per_elem is not None:
        return m.fused_flops_per_elem
    return m.flops_per_elem


#: collectives that sum payloads in wire form mid-flight — integer-only
#: codecs can't ride them (their wire form is not additive)
REDUCING = frozenset({"allreduce", "reduce_scatter"})


def admissible(name: str, collective, error_budget: float,
               integer_payload: bool = False) -> bool:
    """Whether one codec may carry one payload under one error budget.

    Three gates compose the domain check:
      * the codec's stated bound must fit the budget;
      * an ``integer_only`` codec needs an integer payload and a
        non-reducing collective (``collective=None`` skips that last
        check for callers without a collective in hand);
      * a lossy codec never touches an integer payload (token ids and
        indices must survive bit-exact).
    """
    m = meta(name)
    if m.error_bound > float(error_budget):
        return False
    if m.integer_only:
        return bool(integer_payload) and (collective is None
                                          or collective not in REDUCING)
    return m.lossless or not integer_payload


def for_budget(error_budget: float, collective=None,
               integer_payload: bool = False) -> Tuple[str, ...]:
    """Codec names admissible under ``error_budget`` (see
    :func:`admissible` for the domain gates). ``error_budget=0.0`` with a
    float payload -> lossless non-integer codecs only (the selector can
    provably never emit a lossy plan); an integer payload additionally
    admits the integer-only lossless codecs on non-reducing collectives."""
    return tuple(n for n in codecs()
                 if admissible(n, collective, error_budget, integer_payload))


def collective_tolerance(name: str, collective: str, world: int,
                         max_abs: float) -> float:
    """Absolute error tolerance for one compressed collective result.

    Derived from the codec's stated elementwise bound ``eps`` and how the
    compressed execution (``core.mcoll``) accumulates it:

      * allgather / alltoall: one encode/decode round trip -> ``eps * A``;
      * broadcast / scatter: the root encodes once and the tree forwards
        the wire form verbatim -> one round trip, ``eps * A``;
      * reduce_scatter: one encode per sender, errors sum over the
        ``world`` contributions -> ``eps * world * A``;
      * allreduce: sender residuals sum over ``world`` contributions
        (values up to ``n_local * A`` after the intra reduce), plus one
        requantization of the reduced slice -> ``2 * eps * world * A``.

    ``A`` is the max-abs of the *input* payload. Lossless codecs return 0.
    """
    eps = meta(name).error_bound
    if eps == 0.0:
        return 0.0
    factor = {"allgather": 1.0, "alltoall": 1.0,
              "broadcast": 1.0, "scatter": 1.0,
              "reduce_scatter": float(world),
              "allreduce": 2.0 * float(world)}.get(collective)
    if factor is None:
        raise ValueError(f"no compressed execution for {collective!r}")
    return eps * factor * float(max_abs)


# ---------------------------------------------------------------------------
# int8 tree-level helpers (the original optim.compress API, now thin
# adapters over the registry — one error-feedback code path)
# ---------------------------------------------------------------------------


def init_error_state(grads):
    """Zero-initialized error-feedback state matching a gradient tree
    (the carried-residual input to :meth:`Codec.encode_with_feedback`)."""
    return jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads)


def compress_tree(grads, error_state):
    """Quantize every leaf after adding carried error feedback.

    Returns ((qs, scales) list-trees aligned with grads, new_error_state).
    Each leaf rides :meth:`Codec.encode_with_feedback` on the registered
    int8 codec — the same (fused, when enabled) code path the compressed
    collectives use, not a parallel reimplementation."""
    leaves, treedef = jax.tree.flatten(grads)
    err_leaves = jax.tree.leaves(error_state)
    qs, scales, new_err = [], [], []
    for g, e in zip(leaves, err_leaves):
        comp, resid = _INT8.encode_with_feedback(
            jnp.asarray(g).reshape(1, -1), jnp.asarray(e).reshape(1, -1))
        qs.append(comp["q"][0])
        scales.append(comp["scale"][0])
        new_err.append(resid[0].reshape(g.shape))
    return (qs, scales, treedef), jax.tree.unflatten(treedef, new_err)


def decompress_tree(compressed, shapes_like):
    qs, scales, treedef = compressed
    shape_leaves = [l.shape for l in jax.tree.leaves(shapes_like)]
    out = [dequantize(q, s, shp)
           for q, s, shp in zip(qs, scales, shape_leaves)]
    return jax.tree.unflatten(treedef, out)


def wire_bytes(compressed) -> int:
    qs, scales, _ = compressed
    return sum(_INT8.wire_bytes({"q": q, "scale": s})
               for q, s in zip(qs, scales))
