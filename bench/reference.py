"""Plain float32 reference of the served model, layer by layer as the
configuration's block (``bench/blocks/<block>.py``) computes it, with the
pieces every block shares: the embedding, RMSNorm, rotary embeddings, the
head, and weights and caches at the precision a request states.

It follows the precision a request states, and nothing of how the program
computes it: weights of ``w_bits < 16`` are symmetric per-output-channel
integers of the bf16 weights (absmax over the input dimension, the head
included, the embedding not); keys and values of ``kv_bits < 16`` are
symmetric integers per token and head (absmax over the head dimension), and
bf16 at ``kv_bits == 16``.  Everything else is float32 at ``highest``
matmul precision.  It imports nothing of the program.

A sampled request follows the sampling the serving API documents: the
nucleus keeps the most probable tokens of ``softmax(logits)`` while the mass
before each is short of ``top_p``; the kept logits are sampled at
``logits / temperature`` by the inverse of their distribution's CDF, summed
in token-id order, at one uniform per position, drawn from the key
``fold_in(fold_in(PRNGKey(seed), position), 0)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def quantize_weight(w: jnp.ndarray, bits: int) -> jnp.ndarray:
    """[K, N] -> the dequantized float32 values of its int``bits`` form.

    The scale is a true division by ``qmax``: ``qmax`` comes in traced, since
    XLA may turn a division by a constant into a multiplication by its
    reciprocal, which moves a scale by an ulp and flips codes that sit on a
    rounding tie (bf16 weights put many there)."""
    w = w.astype(jnp.float32)
    if bits >= 16:
        return w
    qmax = 2 ** (bits - 1) - 1
    amax = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True), 1e-30)
    scale = amax / _traced(qmax)
    return jnp.clip(jnp.round(w / scale), -qmax - 1, qmax) * scale


def _traced(x: float) -> jnp.ndarray:
    """``x`` as a value XLA cannot fold into a constant."""
    return jax.lax.optimization_barrier(jnp.float32(x))


def quantize_kv(x: jnp.ndarray, bits: int) -> jnp.ndarray:
    """[..., hd] keys or values as the cache holds them, in float32."""
    if bits >= 16:
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    qmax = 2 ** (bits - 1) - 1
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-30) / qmax
    return jnp.clip(jnp.round(x / scale), -qmax - 1, qmax) * scale


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def rope(x, theta):
    """x [T, H, D]; position t rotates pair (i, i + D/2) by t / theta^(2i/D)."""
    t, _, d = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("bits", "eps"))
def head(x: jnp.ndarray, final_norm, unembed, bits: int, eps: float) -> jnp.ndarray:
    """Logits [n, V] of the final hidden states x [n, d]."""
    xn = rms_norm(x, final_norm.astype(jnp.float32), eps)
    return jnp.matmul(xn, quantize_weight(unembed, bits), precision=HI)


def _padded(n: int, pad: int, fixed: int | None) -> int:
    if fixed is not None:
        if n > fixed:
            raise ValueError(f"{n} rows exceed the fixed length {fixed}")
        return fixed
    return -(-n // pad) * pad


def logits(weights: dict, m: dict, seqs: list[np.ndarray], positions: list[np.ndarray],
           w_bits: int, kv_bits: int, pad: int = 256, seq_len: int | None = None,
           pos_len: int | None = None) -> list[np.ndarray]:
    """Reference logits of each sequence at the given positions, layer by
    layer so that one layer's float32 weights are live at a time.  Sequences
    and positions are padded to a multiple of ``pad``, or to ``seq_len`` and
    ``pos_len`` where given, so that a run compiles one shape of each."""
    from bench import blocks  # here: the block modules import this one

    block = blocks.of(m)
    embed = weights["embed"]
    xs = []
    for s in seqs:
        t = _padded(len(s), pad, seq_len)
        tok = np.zeros(t, np.int32)
        tok[: len(s)] = s
        xs.append(embed[jnp.asarray(tok)].astype(jnp.float32))
    for li in range(m["num_hidden_layers"]):
        p = block.layer_weights(weights["blocks"], jnp.int32(li), w_bits)
        xs = [block.layer(x, p, m, kv_bits) for x in xs]
        del p
    out = []
    for x, pos in zip(xs, positions):
        n = len(pos)
        at = np.zeros(_padded(n, pad, pos_len), np.int32)
        at[:n] = pos
        h = head(x[jnp.asarray(at)], weights["final_norm"], weights["unembed"],
                 w_bits, float(m["rms_norm_eps"]))
        out.append(np.asarray(h[:n]))
    return out


@jax.jit
def _uniforms(seed, positions):
    keys = jax.vmap(
        lambda p: jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(seed), p), 0)
    )(positions)
    return jax.vmap(lambda k: jax.random.uniform(k, ()))(keys)


def uniforms(seed: int, n: int) -> np.ndarray:
    """The uniform a request seeded ``seed`` draws at positions 0..n-1."""
    pad = -(-n // 256) * 256
    u = _uniforms(jnp.uint32(seed), jnp.arange(pad, dtype=jnp.int32))
    return np.asarray(u, np.float64)[:n]


def draw_distance(logits: np.ndarray, served: np.ndarray, u: np.ndarray,
                  temperature: float, top_p: float, rows: int = 32) -> np.ndarray:
    """Per position, how far the uniform ``u`` lies outside the interval of
    the CDF that the served token covers in the reference's sampling
    distribution: 0 where the reference draws the same token.  In float64."""
    out = np.empty(len(served))
    for i in range(0, len(served), rows):
        z = logits[i : i + rows].astype(np.float64)
        s = served[i : i + rows]
        p = np.exp(z - z.max(axis=-1, keepdims=True))
        p /= p.sum(axis=-1, keepdims=True)
        order = np.argsort(-z, axis=-1, kind="stable")
        sp = np.take_along_axis(p, order, axis=-1)
        keep = np.zeros(z.shape, bool)
        np.put_along_axis(keep, order, (np.cumsum(sp, axis=-1) - sp) < top_p, axis=-1)
        w = np.where(keep, np.exp((z - z.max(axis=-1, keepdims=True)) / temperature), 0.0)
        cdf = np.cumsum(w, axis=-1)
        cdf /= cdf[:, -1:]
        r = np.arange(len(s))
        hi = cdf[r, s]
        lo = np.where(s > 0, cdf[r, np.maximum(s - 1, 0)], 0.0)
        uu = u[i : i + rows]
        out[i : i + rows] = np.maximum(0.0, np.maximum(lo - uu, uu - hi))
    return out
