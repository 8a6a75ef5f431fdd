"""Jitted public wrappers for the Pallas kernels with backend dispatch:
compiled Pallas on TPU, interpret mode on the CPU backend, and the pure-jnp
ref as the oracle. A shape the kernel's tiling does not divide falls back
to the ref on the CPU only; on a TPU it raises, so a slow path never hides
behind a kernel name."""
from __future__ import annotations

import jax

from repro.kernels import flash_decode as _fd
from repro.kernels import mamba_scan as _ms
from repro.kernels import rwkv6_wkv as _rw
from repro.kernels import staging as _st
from repro.kernels import ref


def interpret() -> bool:
    """Whether Pallas kernels run interpreted: only on the CPU backend.
    Every kernel entry point takes this from here."""
    return jax.default_backend() == "cpu"


def _untiled(kernel: str, why: str) -> None:
    """Allow the ref fallback for an untiled shape on the CPU only."""
    if not interpret():
        raise ValueError(f"{kernel}: {why}; the kernel has no tiling for "
                         f"this shape on {jax.default_backend()}")


def flash_decode(q, k, v, cur_index, chunk: int = 512):
    S = k.shape[1]
    if S % min(chunk, S):
        _untiled("flash_decode", f"cache length {S} % chunk {chunk}")
        return ref.flash_decode(q, k, v, cur_index)
    return _fd.flash_decode(q, k, v, cur_index, chunk=chunk,
                            interpret=interpret())


def rwkv6_wkv(r, k, v, w, u, s0, chunk: int = 128):
    T = r.shape[1]
    if T % min(chunk, T):
        _untiled("rwkv6_wkv", f"sequence {T} % chunk {chunk}")
        return ref.rwkv6_wkv(r, k, v, w, u, s0)
    return _rw.rwkv6_wkv(r, k, v, w, u, s0, chunk=chunk,
                         interpret=interpret())


def mamba_scan(dt, A, Bm, Cm, x, chunk: int = 128, dblk: int = 256):
    T, Di = dt.shape[1], dt.shape[2]
    if T % min(chunk, T) or Di % min(dblk, Di):
        _untiled("mamba_scan",
                 f"(T={T}, Di={Di}) % (chunk={chunk}, dblk={dblk})")
        return ref.mamba_scan(dt, A, Bm, Cm, x)
    return _ms.mamba_scan(dt, A, Bm, Cm, x, chunk=chunk, dblk=dblk,
                          interpret=interpret())


def shift_blocks(v, shift):
    return _st.shift_blocks(v, shift, interpret=interpret())


def pack_blocks(src, idx):
    return _st.pack_blocks(src, idx, interpret=interpret())
