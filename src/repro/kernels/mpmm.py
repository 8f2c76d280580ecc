"""Multi-precision matmul Pallas kernel — the SAU adapted to the TPU MXU.

SPEED's PE combines sixteen 4-bit multipliers into 1x16b / 4x8b / 16x4b MACs
(paper Sec. II-B).  The MXU's native integer granule is int8xint8->int32, so
the TPU-faithful adaptation applies the *same* split-and-combine identity at
radix 256 instead of radix 16:

    v = sum_d plane_d(v) * 256^d      (int8 digit planes, low planes biased)
    x @ w = sum_{d,e} (plane_d(x) @ plane_e(w)) << 8(d+e)   (+ bias terms)

so a 16-bit matmul runs as 4 int8 MXU passes (2 when only one side is 16-bit)
— exactly the paper's "dynamically combined multipliers", one level up.  The
memory-side half of the trick also transfers: int4 weights are bit-packed two
per byte in HBM/VMEM (SPEED's unified elements) and unpacked in-register, so
4-bit weights move half the bytes of int8 and a quarter of bf16.

Dataflows (paper Sec. II-C, mapped from convolution to its matmul core):

  * CF (channel-first)      — grid (m, n, k), k innermost: the full K
    reduction accumulates in a VMEM scratch accumulator (the SAU-internal
    accumulation), one output writeback, no partial-sum traffic.
  * FF (feature-map-first)  — grid (k, m, n), k outermost: each K stage
    revisits the whole output, partial sums spill to the HBM-backed output
    block exactly like SPEED's FF spills partials to the VRF.  Buys maximal
    operand residency per stage; pays partial-sum bandwidth.

`core.dataflow`'s selector chooses per matmul geometry (a matmul is a 1x1
conv).  Block shapes keep the working set in VMEM and the MXU dims 128-aligned.

Modes:
  * int mode    — x is int8/int16, output int32 (bit-exact wraparound mod
    2^32, matching 32-bit SAU accumulators); optional fused per-column scale.
  * dequant mode — x is bf16/f32, int4/int8 weights are dequantized
    in-register and fed to the MXU in the x dtype (production weight-only
    quantized serving: W4A16/W8A16).

Oracle: kernels/ref.py::mpmm_ref;  wrapper: kernels/ops.py::mpmm.
"""
from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.quant.pack import int4_nibbles

__all__ = ["mpmm_pallas", "DEFAULT_BLOCKS"]

DEFAULT_BLOCKS = dict(bm=128, bn=128, bk=512)


def _unpack_w4(packed: jnp.ndarray) -> jnp.ndarray:
    """[bk//2, bn] int8 (two nibbles per byte along K) -> [bk, bn] int8."""
    lo, hi = int4_nibbles(packed)
    k2, n = packed.shape[0] * 2, packed.shape[1]
    return jnp.stack([lo, hi], axis=1).reshape(k2, n).astype(jnp.int8)


def _digit_planes(v: jnp.ndarray, bits: int):
    """Radix-256 digit planes [(int8 array, shift, bias)], value = arr + bias.

    Low planes carry unsigned bytes re-biased into int8 range (arr = byte-128,
    bias = +128) because the MXU multiplies signed int8; the bias terms are
    reconstructed from row/column sums (see _plane_dot)."""
    if bits <= 8:
        return [(v.astype(jnp.int8), 0, 0)]
    assert bits == 16
    v32 = v.astype(jnp.int32)
    lo = (v32 & 0xFF) - 128  # [-128, 127]
    hi = v32 >> 8  # signed high byte
    return [(lo.astype(jnp.int8), 0, 128), (hi.astype(jnp.int8), 8, 0)]


def _plane_dot(x_planes, w_planes, k_len: int) -> jnp.ndarray:
    """sum_{d,e} (x_d + bx)(w_e + bw) << (sx+se), int32 wraparound."""
    out = None
    for xa, sx, bx in x_planes:
        xs = None  # row sums, computed lazily
        for wa, sw, bw in w_planes:
            part = jax.lax.dot_general(
                xa,
                wa,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )
            if bw:
                if xs is None:
                    xs = jnp.sum(xa.astype(jnp.int32), axis=1, keepdims=True)
                part = part + bw * xs
            if bx:
                ws = jnp.sum(wa.astype(jnp.int32), axis=0, keepdims=True)
                part = part + bx * ws
            if bx and bw:
                part = part + bx * bw * k_len
            shift = sx + sw
            if shift:
                part = part << shift
            out = part if out is None else out + part
    return out


def _load_w(w_ref, w_bits: int) -> jnp.ndarray:
    w = w_ref[...]
    if w_bits == 4:
        w = _unpack_w4(w)
    return w


# ----------------------------------------------------------------- CF kernel
def _mpmm_cf_kernel(
    x_ref, w_ref, s_ref, o_ref, acc_ref, *, w_bits, x_bits, mode, n_k, bk
):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _load_w(w_ref, w_bits)
    if mode == "int":
        acc_ref[...] += _plane_dot(
            _digit_planes(x_ref[...], x_bits),
            _digit_planes(w, min(w_bits, 16)),
            k_len=bk,
        )
    else:  # dequant: int weights -> x dtype, MXU dot in float
        x = x_ref[...]
        acc_ref[...] += jax.lax.dot_general(
            x,
            w.astype(x.dtype),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(pl.program_id(2) == n_k - 1)
    def _done():
        acc = acc_ref[...]
        if mode == "int":
            o_ref[...] = acc
        else:
            o_ref[...] = (acc * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


# ----------------------------------------------------------------- FF kernel
def _mpmm_ff_kernel(x_ref, w_ref, o_ref, *, w_bits, x_bits, mode, bk):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    w = _load_w(w_ref, w_bits)
    if mode == "int":
        o_ref[...] += _plane_dot(
            _digit_planes(x_ref[...], x_bits),
            _digit_planes(w, min(w_bits, 16)),
            k_len=bk,
        )
    else:
        # the FF output block IS the cross-K-stage accumulator, so it must
        # be f32 (out_shape below): accumulating spilled partials in the
        # bf16 activation dtype loses ~8 mantissa bits per stage and
        # diverges from the CF path's f32 VMEM accumulator at large K
        x = x_ref[...]
        o_ref[...] += jax.lax.dot_general(
            x,
            w.astype(x.dtype),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )


def mpmm_pallas(
    x: jnp.ndarray,
    w_data: jnp.ndarray,
    w_scale: jnp.ndarray,
    *,
    w_bits: int,
    x_bits: int = 16,
    mode: Literal["int", "dequant"] = "dequant",
    dataflow: Literal["ff", "cf"] = "cf",
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """Raw kernel entry: shapes must already be multiples of the blocks.

    x: [M, K] (int8/int16 in int mode; bf16/f32 in dequant mode)
    w_data: [K, N] int8/int16, or [K//2, N] int8 bit-packed when w_bits == 4
    w_scale: [1, N] f32 per-output-channel scale (fused only in CF+dequant)

    Returns x.dtype for CF dequant (scale fused in-kernel), f32 for FF
    dequant (unscaled cross-stage accumulator — the wrapper applies the
    scale in f32 and casts), int32 for int mode.
    """
    m_sz, k_sz = x.shape
    n_sz = w_data.shape[-1]
    kpack = 2 if w_bits == 4 else 1
    assert m_sz % bm == 0 and n_sz % bn == 0 and k_sz % bk == 0, (x.shape, w_data.shape)
    assert w_data.shape[0] * kpack == k_sz
    n_k = k_sz // bk
    if mode == "int":
        out_dtype = jnp.int32
        acc_dtype = jnp.int32
    else:
        out_dtype = x.dtype
        acc_dtype = jnp.float32

    if dataflow == "cf":
        grid = (m_sz // bm, n_sz // bn, n_k)
        kernel = functools.partial(
            _mpmm_cf_kernel, w_bits=w_bits, x_bits=x_bits, mode=mode, n_k=n_k, bk=bk
        )
        out = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),
                pl.BlockSpec((bk // kpack, bn), lambda m, n, k: (k, n)),
                pl.BlockSpec((1, bn), lambda m, n, k: (0, n)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
            out_shape=jax.ShapeDtypeStruct((m_sz, n_sz), out_dtype),
            scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")
            ),
            interpret=interpret,
            name=f"mpmm_cf_w{w_bits}x{x_bits}_{mode}",
        )(x, w_data, w_scale)
        if mode == "int":
            return out  # scale applied by the wrapper (kept integer-pure)
        return out

    # FF: k outermost, output revisited (partial sums spill to the out block).
    # Dequant-mode partials spill at f32 — the caller applies the scale in
    # f32 and casts down, mirroring the CF kernel's f32 VMEM accumulator.
    grid = (n_k, m_sz // bm, n_sz // bn)
    kernel = functools.partial(
        _mpmm_ff_kernel, w_bits=w_bits, x_bits=x_bits, mode=mode, bk=bk
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda k, m, n: (m, k)),
            pl.BlockSpec((bk // kpack, bn), lambda k, m, n: (k, n)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda k, m, n: (m, n)),
        out_shape=jax.ShapeDtypeStruct((m_sz, n_sz), acc_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "parallel", "parallel")
        ),
        interpret=interpret,
        name=f"mpmm_ff_w{w_bits}x{x_bits}_{mode}",
    )(x, w_data)
    return out
