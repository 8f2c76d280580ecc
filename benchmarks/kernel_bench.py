"""Kernel micro-benchmarks (CPU walltime of the XLA path + interpret-mode
validation cost; TPU wall-clock comes from the roofline, not this box).

Measures the framework-level effect the paper sells: int4/int8 weights cut
the bytes a serving matmul moves (2x/4x vs bf16), the quantized KV cache
cuts decode attention traffic, and the paged decode kernel cuts per-token
traffic from table *capacity* to actual *occupancy* (no full-cache gather).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops


def _time(fn, *args, iters=5) -> float:
    """Best-of-N walltime in us: the min is the noise-robust estimator on a
    shared CPU box (scheduler hiccups only ever make a run slower)."""
    fn(*args)  # compile
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6  # us


def mpmm_rows(smoke: bool = False) -> list[tuple]:
    rng = np.random.default_rng(0)
    m, k, n = (64, 256, 256) if smoke else (256, 2048, 2048)
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    out = []
    bytes_bf16 = k * n * 2
    for bits in (8, 4):
        wd, ws = ops.pack_weights(w, bits)
        us = _time(
            lambda xx, dd=wd, ss=ws, b=bits: ops.mpmm(xx, dd, ss, w_bits=b, backend="xla"),
            x,
        )
        wire = wd.size * wd.dtype.itemsize
        out.append((f"mpmm_w{bits}_xla_{m}x{k}x{n}", us, bytes_bf16 / wire))
    return out


def decode_kv_rows(smoke: bool = False) -> list[tuple]:
    rng = np.random.default_rng(0)
    b_, s, hkv, g, d = (2, 512, 2, 2, 32) if smoke else (4, 2048, 4, 4, 64)
    q = jnp.asarray(rng.normal(size=(b_, hkv * g, d)), jnp.float32)
    kv = rng.normal(size=(2, b_, s, hkv, d)).astype(np.float32)
    out = []
    for bits in (8, 4):
        kd, ks = ops.quantize_kv(jnp.asarray(kv[0]), bits)
        vd, vs = ops.quantize_kv(jnp.asarray(kv[1]), bits)
        lengths = jnp.full((b_,), s, jnp.int32)
        from repro.kernels import ref
        from repro.quant.pack import unpack_int4

        kdu = unpack_int4(kd, -1) if bits == 4 else kd
        vdu = unpack_int4(vd, -1) if bits == 4 else vd
        us = _time(
            lambda qq: ref.mqa_decode_ref(qq, kdu, vdu, ks, vs, lengths, sm_scale=0.125),
            q,
        )
        payload_ratio = (2 * b_ * s * hkv * d * 2) / (kd.size + vd.size)
        out.append((f"decode_kv{bits}_s{s}", us, payload_ratio))
    return out


def paged_decode_rows(smoke: bool = False) -> list[tuple]:
    """Paged kernel vs the old full-table gather, across pool occupancy.

    The gather path copies every table slot into a contiguous [B, S, ...]
    view before attending (cost ∝ table capacity); the paged path walks page
    tables in place (cost ∝ occupied length).  ``derived`` reports effective
    GB/s = bytes the path *actually had to touch* (occupied cache positions,
    K+V payload+scales, once) / walltime — so at low occupancy the gather
    path's useless capacity traffic shows up as a collapsing goodput.

    At ~full occupancy the XLA fallback's sequential slot scan can lose to
    one dense gather on CPU (small per-page gathers vectorize worse); that
    overhead is an artifact of the fallback, not the contract — the compiled
    Pallas kernel pays per-page DMA either way and only *skips* dead slots.
    """
    from repro.kernels import ref

    rng = np.random.default_rng(1)
    if smoke:
        b_, hkv, g, d, ps, w = 2, 2, 2, 32, 16, 4
    else:
        b_, hkv, g, d, ps, w = 4, 2, 4, 64, 64, 16
    s = w * ps
    n_pages = b_ * w
    kv_bits = 8
    q = jnp.asarray(rng.normal(size=(b_, hkv * g, d)), jnp.float32)
    kp = jnp.asarray(rng.integers(-127, 128, (1, n_pages, hkv, ps, d)), jnp.int8)
    vp = jnp.asarray(rng.integers(-127, 128, (1, n_pages, hkv, ps, d)), jnp.int8)
    ks = jnp.asarray(rng.random((1, n_pages, hkv, ps)) * 0.1, jnp.float32)
    vs = jnp.asarray(rng.random((1, n_pages, hkv, ps)) * 0.1, jnp.float32)
    nk = jnp.asarray(rng.integers(-127, 128, (b_, hkv, d)), jnp.int8)
    nv = jnp.asarray(rng.integers(-127, 128, (b_, hkv, d)), jnp.int8)
    nks = jnp.asarray(rng.random((b_, hkv)) * 0.1, jnp.float32)
    nvs = jnp.asarray(rng.random((b_, hkv)) * 0.1, jnp.float32)
    tables = jnp.asarray(
        rng.permutation(n_pages).reshape(b_, w).astype(np.int32)
    )
    rows_idx = jnp.arange(b_)
    sm = 1.0 / float(np.sqrt(d))


    @jax.jit
    def gather_path(lengths):
        # the old serve path: copy every table slot, insert, attend densely
        kd = ref.gather_pages(kp, tables)[0].at[rows_idx, lengths].set(nk)
        vd = ref.gather_pages(vp, tables)[0].at[rows_idx, lengths].set(nv)
        ksd = ref.gather_pages(ks, tables)[0].at[rows_idx, lengths].set(nks)
        vsd = ref.gather_pages(vs, tables)[0].at[rows_idx, lengths].set(nvs)
        return ref.mqa_decode_ref(
            q, kd, vd, ksd[..., None], vsd[..., None], lengths + 1, sm_scale=sm
        )

    def paged_path(lengths):
        return ops.paged_mqa_decode(
            q, kp, vp, ks, vs, tables, lengths, 0, nk, nv, nks, nvs,
            kv_bits=kv_bits, backend="xla",
        )

    out = []
    tok_bytes = hkv * (2 * d + 8)  # K+V payload + two f32 scales per position
    for occ in (1.0, 0.5, 0.25):
        ln = max(int(s * occ) - 1, 1)
        lengths = jnp.full((b_,), ln, jnp.int32)
        useful = b_ * (ln + 1) * tok_bytes  # bytes any path must touch
        for name, fn in (("gather", gather_path), ("paged", paged_path)):
            us = _time(fn, lengths, iters=20)  # shared box: noisy, min-of-20
            gbps = useful / (us * 1e-6) / 1e9
            out.append((f"decode_{name}_s{s}_occ{int(occ * 100)}", us, gbps))
    return out


def rows(smoke: bool = False) -> list[tuple]:
    return mpmm_rows(smoke) + decode_kv_rows(smoke) + paged_decode_rows(smoke)


def main() -> None:
    print("name,us_per_call,derived(ratio_or_eff_GBps)")
    for name, us, derived in rows():
        print(f"{name},{us:.1f},{derived:.2f}")


if __name__ == "__main__":
    main()
