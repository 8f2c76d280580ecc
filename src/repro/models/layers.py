"""Shared NN building blocks: norms, RoPE, embeddings, dense / quantized dense.

Parameters are plain nested dicts of jnp arrays.  Sharding is by name-pattern
rules (distributed/sharding.py); activations get explicit
with_sharding_constraint at layer boundaries.  The quantized dense layer is
the paper's technique on the serving path: int4/int8 weights (packed, per
-output-channel scales) through ``kernels.ops.mpmm`` — the Pallas ``mpmm``
kernel on a TPU with no mesh active, the XLA dequant reference
(``kernels/ref.py::mpmm_ref``) elsewhere.  Layer scans hand ``dense`` the
whole stack of a quantized weight and the layer index
(:func:`split_stacked`, :func:`layer_view`), so the kernel reads each layer's
packed bytes where they lie.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

Params = dict[str, Any]


def rms_norm(x: jnp.ndarray, gamma: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps).astype(x.dtype)) * gamma


def init_rms(d: int, dtype=jnp.float32) -> jnp.ndarray:
    return jnp.ones((d,), dtype)


def dense_init(key, d_in: int, d_out: int, dtype=jnp.bfloat16) -> jnp.ndarray:
    scale = 1.0 / np.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype=jnp.bfloat16) -> jnp.ndarray:
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)


@jax.tree_util.register_static
class StaticBits(int):
    """Quantization bit-width carried in the treedef (static, never traced)."""


def mpmm_backend() -> str:
    """Where ``dense`` runs quantized weights: ``"pallas"`` (the ``mpmm``
    kernel) on a TPU with no mesh active, ``"xla"`` (the dequant reference)
    otherwise — a sharded weight cannot enter an unpartitioned custom call,
    and elsewhere than a TPU the reference is the oracle."""
    from repro.distributed import sharding

    if jax.default_backend() == "tpu" and sharding.get_mesh() is None:
        return "pallas"
    return "xla"


def _is_quantized(w) -> bool:
    return isinstance(w, dict) and "bits" in w


def dense(x: jnp.ndarray, w: jnp.ndarray | dict) -> jnp.ndarray:
    """Dense matmul dispatching on plain vs quantized weights.

    Quantized weights are a dict {"data": int payload (packed along K for
    int4), "scale": [1, N] f32, "bits": StaticBits} — the paper's
    multi-precision path — run through ``kernels.ops.mpmm`` on the backend
    :func:`mpmm_backend` picks.  Inside a layer scan the dict holds the
    whole stack ({"data": [L, K (/2), N], "scale": [L, 1, N]}) and a
    ``"layer"`` index (:func:`layer_view`).
    """
    from repro.distributed.sharding import gather_weight

    w = gather_weight(w)
    if _is_quantized(w):
        from repro.kernels.ops import mpmm

        bits = int(w["bits"])
        return mpmm(
            x,
            w["data"],
            w["scale"],
            w.get("layer"),
            w_bits=bits,
            mode="dequant" if bits < 16 else "int",
            backend=mpmm_backend(),
        ).astype(x.dtype)
    return jnp.einsum("...k,kn->...n", x, w.astype(x.dtype))


def split_stacked(blocks: Params) -> tuple[Params, Params]:
    """Split a stacked [L, ...] block tree for a layer scan: ``(xs,
    stacked)``.  Quantized projections (``{"data": [L, K (/2), N], ...}``)
    leave ``xs`` — which the scan slices, one copy per layer — and stay
    whole in ``stacked``, which the scan body closes over; everything else
    (norms, plain weights, expert stacks) stays in ``xs``."""
    xs, stacked = {}, {}
    for name, leaf in blocks.items():
        if _is_quantized(leaf) and leaf["data"].ndim == 3:
            stacked[name] = leaf
        elif isinstance(leaf, dict) and not _is_quantized(leaf):
            xs[name], stacked[name] = split_stacked(leaf)
        else:
            xs[name] = leaf
    return xs, stacked


def layer_view(p: Params, stacked: Params, li) -> Params:
    """Layer ``li``'s block tree inside the scan body: the scan's slice
    ``p`` with each stacked quantized projection put back, whole, beside
    the layer index ``dense`` hands to the kernel."""
    out = dict(p)
    for name, leaf in stacked.items():
        if _is_quantized(leaf):
            out[name] = {**leaf, "layer": li}
        else:
            out[name] = layer_view(p[name], leaf, li)
    return out


def quantize_dense_weight(w: jnp.ndarray, bits: int) -> dict:
    from repro.kernels.ops import pack_weights

    data, scale = pack_weights(w, bits)
    return {"data": data, "scale": scale, "bits": StaticBits(bits)}


# ------------------------------------------------------------------ RoPE ----
def rope_freqs(head_dim: int, theta: float = 10000.0) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: [B, S, H, D], positions: [B, S] int32."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)  # [D/2]
    ang = positions[..., None].astype(jnp.float32) * freqs  # [B, S, D/2]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------- chunked cross-entropy ----
def chunked_cross_entropy(
    h: jnp.ndarray,  # [B, S, D] final hidden states
    unembed: jnp.ndarray,  # [D, Vpad]
    labels: jnp.ndarray,  # [B, S] int32
    mask: jnp.ndarray | None = None,  # [B, S]
    vocab: int | None = None,  # real vocab (pad logits masked out)
    max_chunk_elems: int = 1 << 28,
) -> jnp.ndarray:
    """Cross-entropy computed in sequence chunks so the [tokens, V] logits
    tensor never materializes at full length (vocabs here reach 262k).

    Chunk length adapts so one chunk's logits stay under ~max_chunk_elems
    f32 elements (1 GB at the default) regardless of batch/vocab."""
    b, s, d = h.shape
    v = unembed.shape[-1]
    if mask is None:
        mask = jnp.ones((b, s), jnp.float32)
    from repro.distributed.sharding import get_mesh

    mesh = get_mesh()
    n_dev = 1
    if mesh is not None:
        for sz in mesh.shape.values():
            n_dev *= sz
    # budget is per DEVICE: the logits chunk is sharded over the mesh
    n_chunk = max(1, -(-(b * s * v) // (max_chunk_elems * n_dev)))
    while n_chunk < s and s % n_chunk:
        n_chunk += 1
    n_chunk = min(n_chunk, s)
    chunk = s // n_chunk
    hs = h.reshape(b, n_chunk, chunk, d).swapaxes(0, 1)  # [n, B, c, D]
    ls = labels.reshape(b, n_chunk, chunk).swapaxes(0, 1)
    ms = mask.reshape(b, n_chunk, chunk).swapaxes(0, 1)

    def step(carry, xs):
        hc, lc, mc = xs
        logits = jnp.einsum("bcd,dv->bcv", hc, unembed.astype(hc.dtype))
        logits = logits.astype(jnp.float32)
        if vocab is not None and vocab < v:  # mask embedding-table padding
            pad_mask = jnp.arange(v) < vocab
            logits = jnp.where(pad_mask, logits, -1e30)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        nll = (logz - gold) * mc
        return carry + jnp.sum(nll), None

    total, _ = jax.lax.scan(step, jnp.float32(0.0), (hs, ls, ms))
    return total / jnp.maximum(jnp.sum(mask), 1.0)
