"""Bit-packing for sub-byte operands.

SPEED's unified elements (paper Sec. II-C) pack 16 four-bit operands per
element so one VRF read feeds all sixteen 4-bit multipliers of a PE.  The TPU
analogue is packing two signed int4 operands per int8 byte in HBM/VMEM so one
byte of memory traffic carries two MAC operands — the memory-side half of the
paper's "combine the multipliers" trick.  The Pallas mpmm kernel unpacks
in-register (VMEM) with the same bit ops used here.
"""
from __future__ import annotations

import jax.numpy as jnp

__all__ = ["pack_int4", "unpack_int4", "int4_nibbles", "pack_int4_hi_lo"]


def pack_int4(x: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """Packs signed int4 values (stored in an int8 array, range [-8, 7])
    pairwise along ``axis`` into int8 bytes: even index -> low nibble, odd ->
    high nibble.  The packed axis halves in length.
    """
    x = jnp.asarray(x, jnp.int8)
    axis = axis % x.ndim
    if x.shape[axis] % 2 != 0:
        raise ValueError(f"axis {axis} length {x.shape[axis]} must be even to pack")
    lo = jnp.take(x, jnp.arange(0, x.shape[axis], 2), axis=axis)
    hi = jnp.take(x, jnp.arange(1, x.shape[axis], 2), axis=axis)
    return ((hi.astype(jnp.int8) << 4) | (lo.astype(jnp.int8) & 0x0F)).astype(jnp.int8)


def int4_nibbles(packed: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Packed int8 bytes -> (low, high) sign-extended nibbles as int32 arrays
    of the same shape.  The shifts run in int32: the TPU kernel compiler
    does not legalize int8 shifts, and kernels call this in-register."""
    p = packed.astype(jnp.int32)
    return (p << 28) >> 28, p >> 4


def unpack_int4(packed: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """Inverse of :func:`pack_int4`; returns int8 array of doubled length with
    sign-extended 4-bit values."""
    packed = jnp.asarray(packed, jnp.int8)
    axis = axis % packed.ndim
    lo, hi = int4_nibbles(packed)
    stacked = jnp.stack([lo, hi], axis=axis + 1).astype(jnp.int8)  # [..., n, 2, ...]
    shape = list(packed.shape)
    shape[axis] *= 2
    return stacked.reshape(shape)


def pack_int4_hi_lo(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Splits wider ints into (hi, lo) 4-bit digit planes (int8 storage):
    ``x == hi * 16 + lo`` with lo in [0, 15] unsigned and hi signed — the
    radix-16 digit decomposition the SAU uses for 8-bit operands
    (see core/sau.py).  Used by the w16/w8 nibble-plane kernels."""
    x = jnp.asarray(x, jnp.int32)
    lo = x & 0x0F
    hi = (x - lo) >> 4
    return hi.astype(jnp.int8), lo.astype(jnp.int8)
