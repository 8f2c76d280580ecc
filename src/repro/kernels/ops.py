"""jit'd public wrappers around the Pallas kernels.

These handle what the raw kernels don't: padding to block multiples, block
size selection, FF/CF dataflow selection (via the same core.dataflow selector
the conv mapper uses — a matmul is a 1x1 conv), weight packing/quantization,
KV-cache quantization, and platform dispatch: the platform decides between
compiled Pallas (TPU) and interpret mode (elsewhere).  The paged attention
ops the serving engine calls pick the compiled kernel on a TPU and their
slot-scan XLA fallback elsewhere.  The model's quantized projections call
``mpmm(..., backend="xla")``, the XLA dequant reference, on every platform.
"""
from __future__ import annotations

import functools
from typing import Literal, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dataflow import ConvLayer
from repro.core.isa import Dataflow
from repro.core.precision import Precision
from repro.kernels import mpmm as mpmm_mod
from repro.kernels import mqa_decode as dec_mod
from repro.kernels import paged_decode as paged_mod
from repro.kernels import paged_prefill as paged_prefill_mod
from repro.kernels import ref as ref_mod
from repro.quant.pack import pack_int4

__all__ = [
    "pack_weights",
    "mpmm",
    "select_matmul_dataflow",
    "mpconv",
    "quantize_kv",
    "mqa_decode",
    "paged_mqa_decode",
    "paged_mqa_prefill",
    "paged_mqa_verify",
    "sample_keys",
    "sampling_probs",
    "sample_from_probs",
    "sample_tokens",
]

_INT_DTYPE = {4: jnp.int8, 8: jnp.int8, 16: jnp.int16}


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _paged_backend_default() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "xla"


@jax.jit
def _abs_max(w: jnp.ndarray) -> jnp.ndarray:
    return jnp.maximum(
        jnp.max(jnp.abs(w.astype(jnp.float32)), axis=-2, keepdims=True), 1e-30
    )


@functools.partial(jax.jit, static_argnames=("bits",))
def _quantize(w: jnp.ndarray, scale: jnp.ndarray, bits: int) -> jnp.ndarray:
    prec = Precision.from_bits(bits)
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), prec.spec.qmin, prec.spec.qmax)
    q = q.astype(_INT_DTYPE[bits])
    return pack_int4(q, axis=-2) if bits == 4 else q


def pack_weights(w: jnp.ndarray, bits: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-output-channel symmetric quantization of a [..., K, N] weight
    (leading dims — stacked layers, experts — quantize independently).

    Returns (w_data, w_scale): w_data is [..., K, N] int8/int16, or
    [..., K//2, N] int8 with two K-consecutive nibbles per byte when
    bits == 4 (SPEED's unified elements along the reduction dim); w_scale is
    [..., 1, N] f32.

    The reduction and the quantization are one jitted pass each, so a
    full-width leaf never sits next to f32 copies of itself.  The scale
    division stays outside jit on purpose: inside, XLA turns a division by
    a constant into a multiplication by its reciprocal, which moves some
    scales (and so payloads) by one ulp.
    """
    scale = (_abs_max(w) / Precision.from_bits(bits).spec.qmax).astype(jnp.float32)
    return _quantize(w, scale, bits), scale


def select_matmul_dataflow(m: int, n: int, k: int) -> Dataflow:
    """FF/CF selection for a matmul via the conv cost model (1x1 conv with
    cin=k, cout=n, h*w=m)."""
    from repro.core.perfmodel import select_dataflow

    hw = int(np.sqrt(max(m, 1))) or 1
    layer = ConvLayer("mm", cin=k, cout=n, k=1, h=hw, w=max(m // hw, 1))
    return select_dataflow(layer, Precision.INT8)


def _pad_to(x: jnp.ndarray, mult: tuple[int, ...]) -> jnp.ndarray:
    pads = [(0, (-s) % m_) for s, m_ in zip(x.shape, mult)]
    if any(p[1] for p in pads):
        return jnp.pad(x, pads)
    return x


def _pick_blocks(m: int, n: int, k: int, kpack: int) -> tuple[int, int, int]:
    def shrink(target: int, size: int, align: int) -> int:
        b = min(target, max(align, 1 << (size - 1).bit_length()))
        return max(align, min(b, target))

    bm = shrink(128, m, 8)
    bn = shrink(128, n, 128) if n >= 128 else 128
    bk = shrink(512, k, 128 * kpack)
    return bm, bn, bk


@functools.partial(
    jax.jit,
    static_argnames=("w_bits", "x_bits", "mode", "dataflow", "backend", "interpret"),
)
def mpmm(
    x: jnp.ndarray,
    w_data: jnp.ndarray,
    w_scale: jnp.ndarray,
    *,
    w_bits: int,
    x_bits: int = 16,
    mode: Literal["int", "dequant"] = "dequant",
    dataflow: Literal["ff", "cf", "auto"] = "cf",
    backend: Literal["pallas", "xla"] = "pallas",
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Multi-precision matmul: x [..., K] @ dequant(w) -> [..., N].

    int mode returns f32 = int32_acc * w_scale (int arithmetic inside);
    dequant mode returns x.dtype.
    """
    if interpret is None:
        interpret = _interpret_default()
    if mode == "dequant" and w_bits == 16:
        raise ValueError("w16 requires int mode (bf16 cannot hold int16 exactly)")
    lead = x.shape[:-1]
    k_sz = x.shape[-1]
    kpack = 2 if w_bits == 4 else 1
    n_sz = w_data.shape[-1]
    x2 = x.reshape(-1, k_sz)
    m_sz = x2.shape[0]

    if dataflow == "auto":
        dataflow = (
            "ff" if select_matmul_dataflow(m_sz, n_sz, k_sz) is Dataflow.FF else "cf"
        )

    if backend == "xla":
        out = ref_mod.mpmm_ref(x2, w_data, w_scale, w_bits=w_bits, mode=mode)
        if mode == "int":
            out = out.astype(jnp.float32) * w_scale.astype(jnp.float32)
        return out.reshape(*lead, n_sz)

    bm, bn, bk = _pick_blocks(m_sz, n_sz, k_sz, kpack)
    xp = _pad_to(x2, (bm, bk))
    wp = _pad_to(w_data, (bk // kpack, bn))
    sp = _pad_to(w_scale.reshape(1, -1), (1, bn))
    out = mpmm_mod.mpmm_pallas(
        xp,
        wp,
        sp,
        w_bits=w_bits,
        x_bits=x_bits,
        mode=mode,
        dataflow=dataflow,
        bm=bm,
        bn=bn,
        bk=bk,
        interpret=interpret,
    )
    out = out[:m_sz, :n_sz]
    if mode == "int":
        out = out.astype(jnp.float32) * w_scale.astype(jnp.float32)
    elif dataflow == "ff":
        # FF dequant partials arrive as f32 (the kernel's cross-stage
        # accumulator); apply the scale in f32 like the CF kernel does
        # in-VMEM, then cast once to the activation dtype
        out = (out * w_scale.astype(jnp.float32)).astype(x.dtype)
    return out.reshape(*lead, n_sz)


def mpconv(
    x: jnp.ndarray,  # [N, H, W, Cin]
    w_data: jnp.ndarray,  # packed [K*K*Cin (/2), Cout]
    w_scale: jnp.ndarray,  # [1, Cout]
    *,
    w_bits: int,
    ksize: int,
    stride: int = 1,
    padding: int = 0,
    mode: Literal["int", "dequant"] = "dequant",
    dataflow: Literal["ff", "cf", "auto"] = "auto",
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Multi-precision convolution = patch extraction + the mpmm kernel.

    On the TPU a direct convolution is executed by the MXU as an implicit
    matmul anyway; the FF/CF dataflow choice survives as the contraction loop
    order of the matmul core (see kernels/mpmm.py docstring).  The dataflow
    selector receives the true conv geometry.
    """
    n, h, w, cin = x.shape
    cout = w_data.shape[-1]
    if dataflow == "auto":
        from repro.core.perfmodel import select_dataflow

        layer = ConvLayer("conv", cin=cin, cout=cout, k=ksize, h=h, w=w,
                          stride=stride, padding=padding)
        df = select_dataflow(layer, Precision.from_bits(w_bits))
        dataflow = "ff" if df is Dataflow.FF else "cf"
    if padding:
        x = jnp.pad(x, ((0, 0), (padding, padding), (padding, padding), (0, 0)))
    patches = jax.lax.conv_general_dilated_patches(
        x,
        filter_shape=(ksize, ksize),
        window_strides=(stride, stride),
        padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )  # [N, Ho, Wo, Cin*K*K] with feature order (cin, kh, kw)
    ho, wo = patches.shape[1], patches.shape[2]
    out = mpmm(
        patches.reshape(-1, patches.shape[-1]),
        w_data,
        w_scale,
        w_bits=w_bits,
        x_bits=16 if mode == "int" else 16,
        mode=mode,
        dataflow=dataflow,
        interpret=interpret,
    )
    return out.reshape(n, ho, wo, cout)


def conv_pack_weights(w: jnp.ndarray, bits: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """[Kh, Kw, Cin, Cout] float -> packed ([Cin*Kh*Kw (/2), Cout], [1, Cout])
    matching conv_general_dilated_patches' (cin, kh, kw) feature order."""
    kh, kw, cin, cout = w.shape
    wm = jnp.transpose(w, (2, 0, 1, 3)).reshape(cin * kh * kw, cout)
    return pack_weights(wm, bits)


def quantize_kv(
    kv: jnp.ndarray, bits: int = 8
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """[B, S, Hkv, D] float -> (int8 payload [B,S,Hkv,D or D//2], scale
    [B,S,Hkv,1]) — per-(token, head) symmetric scales."""
    prec = Precision.from_bits(bits)
    amax = jnp.maximum(jnp.max(jnp.abs(kv), axis=-1, keepdims=True), 1e-30)
    scale = (amax / prec.spec.qmax).astype(jnp.float32)
    q = jnp.clip(jnp.round(kv / scale), prec.spec.qmin, prec.spec.qmax).astype(jnp.int8)
    if bits == 4:
        q = pack_int4(q, axis=-1)
    return q, scale


@functools.partial(jax.jit, static_argnames=("kv_bits", "bs", "interpret"))
def mqa_decode(
    q: jnp.ndarray,  # [B, H, D]
    k_data: jnp.ndarray,
    v_data: jnp.ndarray,
    k_scale: jnp.ndarray,
    v_scale: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    kv_bits: int = 8,
    bs: int = 512,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Single-token GQA attention against an int8/int4 KV cache."""
    if interpret is None:
        interpret = _interpret_default()
    b, h, d = q.shape
    hkv = k_data.shape[2]
    # non-multiple widths clamp + pad-and-mask inside the kernel
    qg = q.reshape(b, hkv, h // hkv, d)
    out = dec_mod.mqa_decode_pallas(
        qg,
        k_data,
        v_data,
        k_scale,
        v_scale,
        lengths.astype(jnp.int32),
        kv_bits=kv_bits,
        sm_scale=1.0 / float(np.sqrt(d)),
        bs=bs,
        interpret=interpret,
    )
    return out.reshape(b, h, d)


@functools.partial(jax.jit, static_argnames=("kv_bits", "backend", "interpret"))
def paged_mqa_decode(
    q: jnp.ndarray,  # [B, H, D]
    k_pool: jnp.ndarray,  # [L, P, Hkv, ps, D (/2 if kv_bits==4)]
    v_pool: jnp.ndarray,
    k_scale,  # [L, P, Hkv, ps] f32, or None when kv_bits == 16
    v_scale,
    tables: jnp.ndarray,  # [B, W] int32 page tables (zero-padded)
    lengths: jnp.ndarray,  # [B] int32 — tokens already in the cache
    layer,  # int32 — pool layer to attend against
    new_k: jnp.ndarray,  # [B, Hkv, D (/2)] this step's token, not yet stored
    new_v: jnp.ndarray,
    new_k_scale=None,  # [B, Hkv] f32, or None
    new_v_scale=None,
    *,
    kv_bits: int = 8,
    window=None,  # int or traced scalar (per-layer windows come from scan)
    backend: Optional[Literal["pallas", "xla"]] = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Single-token GQA attention straight against the paged KV pool.

    Reads only the pages each row's table points at (up to its length), and
    folds the step's own not-yet-stored token into the online softmax, so no
    contiguous cache view ever materializes.  ``backend=None`` picks the
    compiled Pallas kernel on TPU and the slot-scan XLA fallback elsewhere
    (compiled Pallas-on-CPU isn't a thing; interpret mode is for correctness
    tests).  Only ``window``'s *presence* is static — its value may be traced.
    """
    if interpret is None:
        interpret = _interpret_default()
    if backend is None:
        backend = _paged_backend_default()
    b, h, d = q.shape
    hkv = k_pool.shape[2]
    qg = q.reshape(b, hkv, h // hkv, d)
    sm_scale = 1.0 / float(np.sqrt(d))
    args = (
        qg,
        k_pool,
        v_pool,
        k_scale,
        v_scale,
        tables.astype(jnp.int32),
        lengths.astype(jnp.int32),
        jnp.asarray(layer, jnp.int32),
        new_k,
        new_v,
        new_k_scale,
        new_v_scale,
    )
    if backend == "xla":
        out = paged_mod.paged_mqa_decode_xla(
            *args, kv_bits=kv_bits, sm_scale=sm_scale, window=window
        )
    else:
        out = paged_mod.paged_mqa_decode_pallas(
            *args,
            kv_bits=kv_bits,
            sm_scale=sm_scale,
            window=window,
            interpret=interpret,
        )
    return out.reshape(b, h, d)


@functools.partial(jax.jit, static_argnames=("kv_bits", "backend", "interpret"))
def paged_mqa_prefill(
    q: jnp.ndarray,  # [B, C, H, D] — a chunk of C query tokens per row
    k_pool: jnp.ndarray,  # [L, P, Hkv, ps, D (/2 if kv_bits==4)]
    v_pool: jnp.ndarray,
    k_scale,  # [L, P, Hkv, ps] f32, or None when kv_bits == 16
    v_scale,
    tables: jnp.ndarray,  # [B, W] int32 page tables (zero-padded)
    ctx_lens: jnp.ndarray,  # [B] int32 — tokens already in the pool
    q_lens: jnp.ndarray,  # [B] int32 — valid chunk tokens per row
    layer,  # int32 — pool layer to attend against
    chunk_k: jnp.ndarray,  # [B, C, Hkv, D (/2)] this chunk's K, not yet stored
    chunk_v: jnp.ndarray,
    chunk_k_scale=None,  # [B, C, Hkv] f32, or None
    chunk_v_scale=None,
    *,
    kv_bits: int = 8,
    window=None,  # int or traced scalar (per-layer windows come from scan)
    backend: Optional[Literal["pallas", "xla"]] = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Chunked-prefill GQA attention straight against the paged KV pool.

    Chunk token c (absolute position ctx_lens[b] + c) attends to the pages
    holding each row's ctx_lens[b] cached tokens plus the chunk itself under
    a causal-within-chunk mask; rows may be padded (q_lens < C).  Same
    dispatch contract as :func:`paged_mqa_decode`."""
    if interpret is None:
        interpret = _interpret_default()
    if backend is None:
        backend = _paged_backend_default()
    b, c, h, d = q.shape
    hkv = k_pool.shape[2]
    # [B, C, H, D] -> [B, Hkv, C, G, D]; chunk K/V [B, C, Hkv, ...] -> [B, Hkv, C, ...]
    qg = q.reshape(b, c, hkv, h // hkv, d).transpose(0, 2, 1, 3, 4)
    t = lambda x: None if x is None else jnp.swapaxes(x, 1, 2)
    sm_scale = 1.0 / float(np.sqrt(d))
    args = (
        qg,
        k_pool,
        v_pool,
        k_scale,
        v_scale,
        tables.astype(jnp.int32),
        ctx_lens.astype(jnp.int32),
        q_lens.astype(jnp.int32),
        jnp.asarray(layer, jnp.int32),
        t(chunk_k),
        t(chunk_v),
        t(chunk_k_scale),
        t(chunk_v_scale),
    )
    if backend == "xla":
        out = paged_prefill_mod.paged_mqa_prefill_xla(
            *args, kv_bits=kv_bits, sm_scale=sm_scale, window=window
        )
    else:
        out = paged_prefill_mod.paged_mqa_prefill_pallas(
            *args,
            kv_bits=kv_bits,
            sm_scale=sm_scale,
            window=window,
            interpret=interpret,
        )
    return out.transpose(0, 2, 1, 3, 4).reshape(b, c, h, d)


# --------------------------------------------------------------- sampling ops
# Seeded stochastic sampling for the serving engine.  All four ops are
# row-batched (every request in a decode group carries its own temperature /
# top_k / top_p / PRNG key), run inside the engine's jitted hot paths, and
# reduce EXACTLY to greedy argmax when temperature <= 0 — the engine's
# recompute-on-preemption invariant and the greedy golden streams depend on
# that.  Keys are derived as fold_in(fold_in(PRNGKey(seed), position), salt),
# so the token emitted at stream position p depends only on (seed, p) — never
# on batch composition, bucketing, or how many times the request was
# preempted and replayed.


def sample_keys(seeds: jnp.ndarray, positions: jnp.ndarray, salt: int = 0):
    """[B] seeds + [B] stream positions -> [B, 2] per-row PRNG keys.

    ``salt`` separates the independent draws one emission position needs
    (serve/spec_decode.py uses distinct salts for the draft sample, the
    accept uniform and the residual resample at the same position).
    """
    def mk(s, p):
        k = jax.random.PRNGKey(s)
        k = jax.random.fold_in(k, p)
        return jax.random.fold_in(k, salt)

    return jax.vmap(mk)(
        jnp.asarray(seeds, jnp.uint32), jnp.asarray(positions, jnp.int32)
    )


def _top_kp_mask(
    logits: jnp.ndarray, top_k: jnp.ndarray, top_p: jnp.ndarray
) -> jnp.ndarray:
    """[B, V] keep-mask: top-k by logit rank, then nucleus top-p on the
    renormalized surviving distribution (HF warper order).  top_k <= 0 and
    top_p >= 1 disable their stage; the most probable token always survives.
    One descending argsort drives both stages (sorted-domain ranks and
    cumulative mass), scattered back to vocab order at the end.
    """
    b, v = logits.shape
    k = jnp.clip(jnp.where(top_k > 0, top_k, v), 1, v)
    order = jnp.argsort(logits, axis=-1)[:, ::-1]  # descending
    sl = jnp.take_along_axis(logits, order, axis=-1)
    ranks = jnp.arange(v, dtype=jnp.int32)[None, :]
    keep_k = ranks < k[:, None]
    probs = jax.nn.softmax(jnp.where(keep_k, sl, -jnp.inf), axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # keep the smallest prefix reaching top_p: a token survives while the
    # mass *before* it is still short of top_p (the first always is).  At
    # the disabled value top_p >= 1 keep everything explicitly — f32 tail
    # mass can round `cum - probs` up to exactly 1.0, which the strict
    # `< 1.0` test would mask, breaking the elided==masked equivalence
    keep_p = ((cum - probs) < top_p[:, None]) | (top_p[:, None] >= 1.0)
    keep = keep_k & keep_p
    rows = jnp.arange(b)
    return jnp.zeros((b, v), bool).at[rows[:, None], order].set(keep)


def _masked_logits(logits, top_k, top_p):
    """Apply the top-k/top-p mask; ``top_k=None`` / ``top_p=None`` elide the
    corresponding stage STATICALLY — a temperature-only sampling graph never
    pays the vocab argsort (the engine passes None when no row in a group
    uses the knob)."""
    if top_k is None and top_p is None:
        return logits
    b = logits.shape[0]
    if top_k is None:
        top_k = jnp.zeros(b, jnp.int32)
    if top_p is None:
        top_p = jnp.ones(b, jnp.float32)
    return jnp.where(_top_kp_mask(logits, top_k, top_p), logits, -jnp.inf)


def sampling_probs(
    logits: jnp.ndarray,  # [B, V] f32
    temperature: jnp.ndarray,  # [B] f32; <= 0 means greedy
    top_k=None,  # [B] i32 (<= 0 disables) or None (statically disabled)
    top_p=None,  # [B] f32 (>= 1 disables) or None (statically disabled)
) -> jnp.ndarray:
    """[B, V] exact per-row sampling distribution after top-k -> top-p ->
    temperature: softmax(masked_logits / temperature), a one-hot at the raw
    argmax for greedy rows.  This is the distribution ``sample_tokens`` draws
    from, and what speculative rejection sampling uses for the accept ratio
    and residual (serve/spec_decode.py)."""
    greedy = temperature <= 0.0
    t = jnp.maximum(temperature, 1e-6)[:, None]
    probs = jax.nn.softmax(_masked_logits(logits, top_k, top_p) / t, axis=-1)
    onehot = jax.nn.one_hot(
        jnp.argmax(logits, axis=-1), logits.shape[-1], dtype=probs.dtype
    )
    return jnp.where(greedy[:, None], onehot, probs)


def _inverse_cdf(probs: jnp.ndarray, u: jnp.ndarray) -> jnp.ndarray:
    """[B, V] probs + [B] uniforms -> [B] sampled indices.

    Two-level search: block partial sums (one O(V) pass), a cumsum over the
    ~sqrt(V) block totals, then a cumsum inside the one selected block per
    row.  A flat jnp.cumsum over the vocab axis lowers to an O(V^2)-ish
    reduce-window on CPU XLA (hundreds of us at V=1024 — comparable to a
    small model's whole decode step); the blocked form keeps in-jit sampling
    a <10% overhead on the serving hot path.  Probs needn't be normalized
    (the threshold is scaled by the row total); zero-probability tokens are
    never drawn, so a one-hot row deterministically returns its hot index.
    """
    b, v = probs.shape
    nb = 1 << ((v - 1).bit_length() + 1) // 2  # ~sqrt(V), power of two
    pad = (-v) % nb
    if pad:
        probs = jnp.pad(probs, ((0, 0), (0, pad)))
    vb = probs.shape[1] // nb
    pb = probs.reshape(b, nb, vb)
    cb = jnp.cumsum(jnp.sum(pb, axis=-1), axis=-1)  # [B, nb] block cdf
    r = u * cb[:, -1]  # [B] threshold in un-normalized mass
    blk = jnp.clip(jnp.sum(cb <= r[:, None], axis=-1), 0, nb - 1)
    base = jnp.where(
        blk > 0,
        jnp.take_along_axis(cb, jnp.maximum(blk - 1, 0)[:, None], 1)[:, 0],
        0.0,
    )
    sub = jnp.take_along_axis(pb, blk[:, None, None], axis=1)[:, 0]  # [B, vb]
    cs = base[:, None] + jnp.cumsum(sub, axis=-1)
    off = jnp.clip(jnp.sum(cs <= r[:, None], axis=-1), 0, vb - 1)
    return jnp.clip(blk * vb + off, 0, v - 1).astype(jnp.int32)


def sample_from_probs(probs: jnp.ndarray, keys: jnp.ndarray) -> jnp.ndarray:
    """Categorical draw per row: [B, V] probs + [B, 2] keys -> [B] int32.

    Inverse-CDF with ONE scalar uniform per row — a per-row gumbel field
    would draw B*V PRNG variates, which dominates a small model's decode
    step on CPU.  Zero-probability tokens are never drawn, and a one-hot row
    (greedy) deterministically returns its hot index whatever the key says."""
    u = jax.vmap(lambda k: jax.random.uniform(k, ()))(keys)
    return _inverse_cdf(probs, u)


def sample_tokens(
    logits: jnp.ndarray,  # [B, V] f32
    keys: jnp.ndarray,  # [B, 2] per-row keys (see sample_keys)
    temperature: jnp.ndarray,  # [B] f32; <= 0 means greedy
    top_k=None,  # [B] i32 (<= 0 disables) or None (statically disabled)
    top_p=None,  # [B] f32 (>= 1 disables) or None (statically disabled)
) -> jnp.ndarray:
    """[B] int32 next tokens: greedy rows are the exact raw argmax (bit-equal
    to the pre-sampling engine), sampled rows draw from exactly
    :func:`sampling_probs`' distribution (inverse-CDF over the masked scaled
    softmax, one uniform per row).  The masked and mask-elided graphs draw
    identical tokens for rows whose top_k/top_p are at their disabled values
    (the mask keeps everything and the uniform is key-determined)."""
    greedy = temperature <= 0.0
    t = jnp.maximum(temperature, 1e-6)[:, None]
    masked = _masked_logits(logits, top_k, top_p)
    # unnormalized exp suffices: _inverse_cdf scales its threshold by the
    # row total, saving softmax's divide pass over the vocab
    w = jnp.exp((masked - jnp.max(masked, axis=-1, keepdims=True)) / t)
    u = jax.vmap(lambda k: jax.random.uniform(k, ()))(keys)
    sampled = _inverse_cdf(w, u)
    return jnp.where(
        greedy, jnp.argmax(logits, axis=-1), sampled
    ).astype(jnp.int32)


def paged_mqa_verify(*args, **kwargs) -> jnp.ndarray:
    """Multi-token verify attention for speculative decoding.

    A speculative verify window *is* a causal self-chunk: the window's C
    tokens (the last emitted token + the draft tokens) sit at absolute
    positions ``ctx_lens[b] + c``, attend to every pooled token before the
    window through the page tables, and to each other under the
    causal-within-chunk mask — exactly the :func:`paged_mqa_prefill`
    contract, so no new attention kernel is needed.  The caller scatters the
    window's target-precision K/V into its pages (overwriting the draft
    passes' K/V) and rolls rejected tail positions back host-side via
    ``cache_len`` truncation, so nothing stale is ever attended.
    """
    return paged_mqa_prefill(*args, **kwargs)
