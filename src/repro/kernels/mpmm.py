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
per byte in HBM/VMEM (SPEED's unified elements) and split into their nibbles
in-register, so 4-bit weights move half the bytes of int8 and a quarter of
bf16.  Byte ``i`` of a packed column holds rows ``2i`` (low nibble) and
``2i + 1`` (high nibble), so the kernel never interleaves the nibbles back
into K order: the wrapper splits the (small) activation into its even and odd
K columns and the kernel computes ``x_even @ lo + x_odd @ hi`` — two half-K
MXU dots on the same f32 accumulator.

The weight is read where it lies: ``w_data`` may be a stack of layers
``[L, K/kpack, N]`` (the serving path's layer scan) and a traced layer index
is scalar-prefetched into the weight's and scale's block index maps, so no
sliced or dequantized copy of a weight is ever written to HBM.  A plain
``[K/kpack, N]`` weight is a stack of one.

Dataflows (paper Sec. II-C, mapped from convolution to its matmul core):

  * CF (channel-first)      — grid (m, n, k), k innermost: the full K
    reduction accumulates in a VMEM scratch accumulator (the SAU-internal
    accumulation), one output writeback, no partial-sum traffic.
  * FF (feature-map-first)  — grid (k, m, n), k outermost: each K stage
    revisits the whole output, partial sums spill to the HBM-backed output
    block exactly like SPEED's FF spills partials to the VRF.  Buys maximal
    operand residency per stage; pays partial-sum bandwidth.

`core.dataflow`'s selector chooses per matmul geometry (a matmul is a 1x1
conv).  Block shapes come from ``kernels/ops.py::_pick_blocks``.

Modes:
  * int mode    — x is int8/int16, output int32 (bit-exact wraparound mod
    2^32, matching 32-bit SAU accumulators); optional fused per-column scale.
  * dequant mode — x is bf16/f32, int4/int8 weights are converted in-register
    and fed to the MXU in the x dtype, the per-channel scale applied to the
    f32 accumulator (production weight-only quantized serving: W4A16/W8A16).

The kernel's device op is named ``mpmm_{cf,ff}_w{bits}x{x_bits}_{mode}``.

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

__all__ = ["mpmm_pallas"]

VMEM_LIMIT = 48 << 20  # scoped VMEM a step may take (v5e has 128 MiB)


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


def _tile_dot(x_refs, w_ref, *, w_bits: int, x_bits: int, mode: str):
    """This grid step's K tile: sum over (x part, weight part) pairs — the
    even/odd activation columns against the low/high nibbles for W4, the one
    activation against the one weight otherwise.  f32 (dequant) or int32
    (int mode) partial product of the output block."""
    w = w_ref[...]
    w_parts = int4_nibbles(w) if w_bits == 4 else (w,)
    out = None
    for x_ref, wp in zip(x_refs, w_parts):
        x = x_ref[...]
        if mode == "int":
            part = _plane_dot(
                _digit_planes(x, x_bits),
                _digit_planes(wp, min(w_bits, 16)),
                k_len=x.shape[1],
            )
        else:  # dequant: int weights -> x dtype, MXU dot in float
            if wp.dtype != jnp.int8:  # nibbles arrive as int32
                wp = wp.astype(jnp.float32)
            part = jax.lax.dot_general(
                x,
                wp.astype(x.dtype),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        out = part if out is None else out + part
    return out


# ----------------------------------------------------------------- CF kernel
def _mpmm_cf_kernel(layer_ref, *refs, n_x, w_bits, x_bits, mode, n_k):
    del layer_ref  # consumed by the index maps
    x_refs = refs[:n_x]
    w_ref, s_ref, o_ref, acc_ref = refs[n_x:]

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _tile_dot(x_refs, w_ref, w_bits=w_bits, x_bits=x_bits, mode=mode)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _done():
        acc = acc_ref[...]
        if mode == "int":
            o_ref[...] = acc
        else:
            o_ref[...] = (acc * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


# ----------------------------------------------------------------- FF kernel
def _mpmm_ff_kernel(layer_ref, *refs, n_x, w_bits, x_bits, mode):
    del layer_ref
    x_refs = refs[:n_x]
    w_ref, o_ref = refs[n_x:]

    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # in dequant mode the FF output block IS the cross-K-stage accumulator,
    # so it is f32 (out_shape below): accumulating spilled partials in the
    # bf16 activation dtype loses ~8 mantissa bits per stage and diverges
    # from the CF path's f32 VMEM accumulator at large K
    o_ref[...] += _tile_dot(x_refs, w_ref, w_bits=w_bits, x_bits=x_bits, mode=mode)


def mpmm_pallas(
    x: jnp.ndarray,
    w_data: jnp.ndarray,
    w_scale: jnp.ndarray,
    layer=None,
    *,
    w_bits: int,
    x_bits: int = 16,
    mode: Literal["int", "dequant"] = "dequant",
    dataflow: Literal["ff", "cf"] = "cf",
    bm: int,
    bn: int,
    bk: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Raw kernel entry: blocks must divide the shapes (or equal them).

    x: [M, K] (int8/int16 in int mode; bf16/f32 in dequant mode)
    w_data: [K, N] int8/int16, or [K//2, N] int8 bit-packed when w_bits == 4;
        or a stack of such weights [L, K (//2), N]
    w_scale: [1, N] f32 per-output-channel scale ([L, 1, N] for a stack;
        fused only in CF+dequant)
    layer: int32 scalar, the stacked layer to multiply by (default 0)
    bk: K extent of one grid step, in unpacked weight rows

    Returns x.dtype for CF dequant (scale fused in-kernel), f32 for FF
    dequant (unscaled cross-stage accumulator — the wrapper applies the
    scale in f32 and casts), int32 for int mode.
    """
    if w_data.ndim == 2:
        w_data, w_scale = w_data[None], w_scale.reshape(1, 1, -1)
    m_sz, k_sz = x.shape
    n_sz = w_data.shape[-1]
    kpack = 2 if w_bits == 4 else 1
    kb = bk // kpack  # packed weight rows (and columns of each x part) a step
    assert w_data.shape[1] * kpack == k_sz, (x.shape, w_data.shape)
    assert m_sz % bm == 0 and n_sz % bn == 0 and k_sz % bk == 0, (
        x.shape, w_data.shape, (bm, bn, bk)
    )
    n_k = k_sz // bk
    # W4: even / odd K columns meet the low / high nibbles (module docstring);
    # a transpose, not a strided slice, which XLA's CPU dot cannot take in bf16
    xs = tuple(jnp.moveaxis(x.reshape(m_sz, -1, 2), 2, 0)) if w_bits == 4 else (x,)
    layer = jnp.asarray(0 if layer is None else layer, jnp.int32).reshape(1)
    if mode == "int":
        out_dtype = acc_dtype = jnp.int32
    else:
        out_dtype, acc_dtype = x.dtype, jnp.float32
    name = f"mpmm_{dataflow}_w{w_bits}x{x_bits}_{mode}"

    if dataflow == "cf":
        x_spec = pl.BlockSpec((bm, kb), lambda m, n, k, li: (m, k))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(m_sz // bm, n_sz // bn, n_k),
            in_specs=[x_spec] * len(xs) + [
                pl.BlockSpec((None, kb, bn), lambda m, n, k, li: (li[0], k, n)),
                pl.BlockSpec((None, 1, bn), lambda m, n, k, li: (li[0], 0, n)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda m, n, k, li: (m, n)),
            scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        )
        kernel = functools.partial(
            _mpmm_cf_kernel, n_x=len(xs), w_bits=w_bits, x_bits=x_bits,
            mode=mode, n_k=n_k,
        )
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((m_sz, n_sz), out_dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=VMEM_LIMIT,
            ),
            interpret=interpret,
            name=name,
        )(layer, *xs, w_data, w_scale)

    # FF: k outermost, output revisited (partial sums spill to the out block).
    # Dequant-mode partials spill at f32 — the caller applies the scale in
    # f32 and casts down, mirroring the CF kernel's f32 VMEM accumulator.
    x_spec = pl.BlockSpec((bm, kb), lambda k, m, n, li: (m, k))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_k, m_sz // bm, n_sz // bn),
        in_specs=[x_spec] * len(xs) + [
            pl.BlockSpec((None, kb, bn), lambda k, m, n, li: (li[0], k, n)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda k, m, n, li: (m, n)),
    )
    kernel = functools.partial(
        _mpmm_ff_kernel, n_x=len(xs), w_bits=w_bits, x_bits=x_bits, mode=mode
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m_sz, n_sz), acc_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        interpret=interpret,
        name=name,
    )(layer, *xs, w_data)
