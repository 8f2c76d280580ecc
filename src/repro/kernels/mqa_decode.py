"""Flash-decode GQA attention over a multi-precision (int8/int4) KV cache.

The serving-side hot spot of the LM fleet: one new token attends to a long
cache.  At 32k-500k context the KV cache dominates HBM traffic, so SPEED's
multi-precision idea is applied where it pays most: keys/values are stored
int8 or bit-packed int4 with per-(token, head) scales and dequantized
in-register, halving/quartering the bytes each decode step must move.

Implementation: classic flash-decoding — grid (batch, kv_head, seq_blocks)
with the sequence dimension innermost/sequential, online-softmax running
(max, denom, acc) state in VMEM scratch, GQA handled by blocking queries as
[groups, head_dim] per kv head.  Length masking supports ragged batches.

Oracle: kernels/ref.py::mqa_decode_ref;  wrapper: kernels/ops.py::mqa_decode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.quant.pack import int4_nibbles

__all__ = ["mqa_decode_pallas"]

_NEG_INF = -1e30


def _unpack_kv4(packed: jnp.ndarray) -> jnp.ndarray:
    """[bs, D//2] int8 -> [bs, D] int32 (nibbles packed along head_dim)."""
    lo, hi = int4_nibbles(packed)
    return jnp.stack([lo, hi], axis=-1).reshape(packed.shape[0], packed.shape[1] * 2)


def _decode_kernel(
    len_ref,  # [1] int32 (SMEM-ish block)
    q_ref,  # [1, 1, G, D]
    k_ref,  # [1, bs, 1, D or D//2]
    v_ref,  # [1, bs, 1, D or D//2]
    ks_ref,  # [1, bs, 1, 1]
    vs_ref,  # [1, bs, 1, 1]
    o_ref,  # [1, 1, G, D]
    m_ref,  # scratch [G, 1] f32
    l_ref,  # scratch [G, 1] f32
    acc_ref,  # scratch [G, D] f32
    *,
    bs: int,
    kv_bits: int,
    sm_scale: float,
    n_s: int,
):
    s_idx = pl.program_id(2)

    @pl.when(s_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    g, d = q_ref.shape[2], q_ref.shape[3]
    q = q_ref[0, 0].astype(jnp.float32)  # [G, D]
    k = k_ref[0, :, 0]  # [bs, D(/2)] int8
    v = v_ref[0, :, 0]
    if kv_bits == 4:
        k = _unpack_kv4(k)
        v = _unpack_kv4(v)
    kf = k.astype(jnp.float32) * ks_ref[0, :, 0].astype(jnp.float32)  # [bs, D]
    vf = v.astype(jnp.float32) * vs_ref[0, :, 0].astype(jnp.float32)

    scores = jax.lax.dot_general(
        q, kf, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [G, bs]
    scores = scores * sm_scale
    # ragged-length masking
    pos = s_idx * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    valid = pos < len_ref[0]  # [1, bs]
    scores = jnp.where(valid, scores, _NEG_INF)

    m_prev = m_ref[...]  # [G, 1]
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new)  # [G, bs]
    p = jnp.where(valid, p, 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, vf, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(s_idx == n_s - 1)
    def _done():
        denom = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def mqa_decode_pallas(
    q: jnp.ndarray,  # [B, Hkv, G, D]
    k_data: jnp.ndarray,  # [B, S, Hkv, D (/2 if kv_bits==4)] int8
    v_data: jnp.ndarray,
    k_scale: jnp.ndarray,  # [B, S, Hkv, 1] f32
    v_scale: jnp.ndarray,
    lengths: jnp.ndarray,  # [B] int32
    *,
    kv_bits: int = 8,
    sm_scale: float,
    bs: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    b, hkv, g, d = q.shape
    s = k_data.shape[1]
    bs = min(bs, s)
    if s % bs:
        # pad-and-mask: callers with non-multiple cache widths (e.g. small
        # page-table widths) get a zero tail that the per-row length mask
        # already excludes — lengths <= s by contract.
        pad = (-s) % bs
        pads = ((0, 0), (0, pad), (0, 0), (0, 0))
        k_data = jnp.pad(k_data, pads)
        v_data = jnp.pad(v_data, pads)
        k_scale = jnp.pad(k_scale, pads)
        v_scale = jnp.pad(v_scale, pads)
        s += pad
    n_s = s // bs
    dk = k_data.shape[-1]
    kernel = functools.partial(
        _decode_kernel, bs=bs, kv_bits=kv_bits, sm_scale=sm_scale, n_s=n_s
    )
    return pl.pallas_call(
        kernel,
        grid=(b, hkv, n_s),
        in_specs=[
            pl.BlockSpec((1,), lambda b_, h_, s_: (b_,)),
            pl.BlockSpec((1, 1, g, d), lambda b_, h_, s_: (b_, h_, 0, 0)),
            pl.BlockSpec((1, bs, 1, dk), lambda b_, h_, s_: (b_, s_, h_, 0)),
            pl.BlockSpec((1, bs, 1, dk), lambda b_, h_, s_: (b_, s_, h_, 0)),
            pl.BlockSpec((1, bs, 1, 1), lambda b_, h_, s_: (b_, s_, h_, 0)),
            pl.BlockSpec((1, bs, 1, 1), lambda b_, h_, s_: (b_, s_, h_, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda b_, h_, s_: (b_, h_, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name=f"mqa_decode_kv{kv_bits}",
    )(lengths, q, k_data, v_data, k_scale, v_scale)
