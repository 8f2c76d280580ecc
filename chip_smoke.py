#!/usr/bin/env python3
"""Smoke test of the serving path on one TPU, at llama3.2-3b full width.

    python3 chip_smoke.py

Runs in one process and exits non-zero, before any phase, where JAX finds
no TPU.  Every phase raises on failure; nothing is caught.

* Phase A: the compiled Pallas paged decode and paged prefill kernels
  against their XLA fallbacks (``paged_mqa_*_xla``) on the same random
  inputs, at llama3.2-3b attention widths and kv4/kv8/kv16, within the
  interpret-mode tests' tolerances.
* Phase B: mixed-precision (W4 + W8), prefix-shared, sampled serving
  through ``repro.launch.serve.main``; checks token counts, finish reasons,
  mixed-precision steps, prefix-cache hits, same-seed reproducibility, and
  that one step program holds the compiled kernels (``tpu_custom_call``).
* Phase C: speculative decoding (bf16 target, W8 draft, greedy); checks
  token counts and that speculative rounds ran, and reports the accept rate
  and the share of tokens that agree with a spec-off run.

Each phase prints its report and its seconds split into compile (tracing,
lowering and XLA compilation, persistent-cache lookups included) and run.
The last stdout line is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

CACHE_DIR = enable_compile_cache()

import jax  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "llama3.2-3b"
PHASE_B = [
    "--arch", ARCH, "--requests", "8", "--batch-size", "4", "--prompt-len", "128",
    "--shared-prefix", "64", "--new-tokens", "32", "--precision-mix", "4,8",
    "--kv-bits", "8", "--temperature", "0.8", "--top-p", "0.95", "--seed", "7",
]
SPEC_OFF = [
    "--arch", ARCH, "--no-quantize", "--requests", "4", "--batch-size", "4",
    "--prompt-len", "64", "--new-tokens", "32",
]
PHASE_C = SPEC_OFF + ["--spec-k", "4", "--draft-bits", "8"]
TOL = dict(atol=3e-3, rtol=3e-3)  # tests/test_kernels_paged.py, _prefill.py

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
_compile_spans: list[tuple[float, float]] = []


def _on_span(event: str, start: float, end: float, **_) -> None:
    if event in _COMPILE_EVENTS:
        _compile_spans.append((start, end))


def _union_s(spans) -> float:
    """Seconds covered by the spans (nested jits trace inside their caller)."""
    total, reach = 0.0, float("-inf")
    for start, end in sorted(spans):
        if end > reach:
            total += end - max(start, reach)
            reach = end
    return total


def timed(name: str, fn):
    """Run one phase; print its wall seconds split into compile and run."""
    n0, t0 = len(_compile_spans), time.perf_counter()
    out = fn()
    wall = time.perf_counter() - t0
    compile_s = _union_s(_compile_spans[n0:])
    print(f"[{name}] seconds: wall {wall:.3f}, compile {compile_s:.3f}, "
          f"run {wall - compile_s:.3f}", flush=True)
    gc.collect()  # drop the phase's engine, weights and pools before the next
    return out


# ------------------------------------------------------------------ phase A
def _paged_inputs(rng, kv_bits: int, b: int, c: int | None):
    """Random head-major pool, shuffled tables, and a query chunk (c tokens,
    or one decode token when c is None), at llama3.2-3b attention widths."""
    from repro.configs import get_config
    from repro.quant.pack import pack_int4

    cfg = get_config(ARCH)
    h, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    n_layers, ps, w = 2, 16, 16
    n_pages = b * w
    lead = (b,) if c is None else (b, c)
    q = rng.normal(size=(*lead, h, d)).astype(np.float32)
    if kv_bits < 16:
        lim = 8 if kv_bits == 4 else 128
        pack = (lambda x: pack_int4(x, axis=-1)) if kv_bits == 4 else (lambda x: x)
        pay = lambda *s: pack(rng.integers(-lim, lim, (*s, d)).astype(np.int8))
        scale = lambda *s: (rng.random(s) * 0.1).astype(np.float32)
    else:
        pay = lambda *s: rng.normal(size=(*s, d)).astype(jax.numpy.bfloat16)
        scale = lambda *s: None
    pool = (n_layers, n_pages, hkv, ps)
    tables = rng.permutation(n_pages).reshape(b, w).astype(np.int32)
    return (
        q, pay(*pool), pay(*pool), scale(*pool), scale(*pool), tables,
        pay(*lead, hkv), pay(*lead, hkv), scale(*lead, hkv), scale(*lead, hkv),
    )


def phase_a() -> dict:
    from repro.kernels import ops

    # the platform picks compiled Pallas on the chip: no interpret mode, no
    # XLA fallback behind the engine's back
    assert ops._interpret_default() is False
    assert ops._paged_backend_default() == "pallas"
    rng = np.random.default_rng(0)
    b, c = 4, 32
    lengths = np.array([256, 48, 0, 137], np.int32)  # full, page edge, empty, ragged
    ctx = np.array([224, 48, 0, 100], np.int32)
    q_lens = np.array([32, 32, 17, 32], np.int32)
    report = {}
    for kv_bits in (4, 8, 16):
        for kind in ("decode", "prefill"):
            if kind == "decode":
                q, kp, vp, ks, vs, tb, nk, nv, nks, nvs = _paged_inputs(rng, kv_bits, b, None)
                run = lambda backend: ops.paged_mqa_decode(
                    q, kp, vp, ks, vs, tb, lengths, 1, nk, nv, nks, nvs,
                    kv_bits=kv_bits, backend=backend,
                )
                rows = lambda x: x
            else:
                q, kp, vp, ks, vs, tb, nk, nv, nks, nvs = _paged_inputs(rng, kv_bits, b, c)
                run = lambda backend: ops.paged_mqa_prefill(
                    q, kp, vp, ks, vs, tb, ctx, q_lens, 1, nk, nv, nks, nvs,
                    kv_bits=kv_bits, backend=backend,
                )
                # padding rows (chunk index >= q_len) are unspecified
                rows = lambda x: np.concatenate([x[i, : q_lens[i]] for i in range(b)])
            # gate at full f32 matmul precision on both sides, so the check
            # is of indexing, masking and dequantization, not of MXU rounding
            with jax.default_matmul_precision("highest"):
                got, want = (rows(np.asarray(run(bk))) for bk in ("pallas", "xla"))
            np.testing.assert_allclose(got, want, **TOL, err_msg=f"{kind} kv{kv_bits}")
            # the engine's own precision: reported, not gated
            got_d, want_d = (rows(np.asarray(run(bk))) for bk in ("pallas", "xla"))
            report[f"{kind}_kv{kv_bits}"] = {
                "max_abs_err_highest": float(np.max(np.abs(got - want))),
                "max_abs_err_default": float(np.max(np.abs(got_d - want_d))),
            }
    print(json.dumps({"phase_a": report}, indent=1), flush=True)
    return report


# ------------------------------------------------------------------ phase B
def phase_b() -> dict:
    from repro.launch import serve
    from repro.serve import engine as engine_mod

    # record the abstract arguments of the first decode call, so the program
    # the engine ran can be compiled again (a persistent-cache hit) and read
    shared_jits = engine_mod._shared_jits
    prefill, decode, chunk, spec = shared_jits()
    seen = {}

    def decode_spy(*args, **kwargs):
        seen.setdefault("args", (jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args
        ), kwargs))
        return decode(*args, **kwargs)

    engine_mod._shared_jits = lambda: (prefill, decode_spy, chunk, spec)
    try:
        first = serve.main(PHASE_B)
        again = serve.main(PHASE_B)
    finally:
        engine_mod._shared_jits = shared_jits
    assert first["tokens_out"] == 256, first["tokens_out"]
    assert all(r == "length" for r in first["finish_reasons"]), first["finish_reasons"]
    assert first["mixed_precision_steps"] > 0
    assert first["prefix_hit_tokens"] > 0
    assert again["outputs"] == first["outputs"], "same seed, different streams"
    args, kwargs = seen["args"]
    hlo = decode.lower(*args, **kwargs).compile().as_text()
    assert "tpu_custom_call" in hlo, "decode step compiled without the Pallas kernel"
    return first


# ------------------------------------------------------------------ phase C
def phase_c() -> dict:
    from repro.launch import serve

    spec = serve.main(PHASE_C)
    assert spec["tokens_out"] == 128, spec["tokens_out"]
    assert spec["spec_rounds"] > 0
    plain = serve.main(SPEC_OFF)
    pairs = [
        (a, b) for sa, sb in zip(spec["outputs"], plain["outputs"]) for a, b in zip(sa, sb)
    ]
    agree = sum(a == b for a, b in pairs) / len(pairs)
    print(json.dumps({"phase_c": {
        "spec_accept_rate": spec["spec_accept_rate"],
        "spec_rounds": spec["spec_rounds"],
        "token_agreement_with_spec_off": agree,
        "tokens_compared": len(pairs),
    }}, indent=1), flush=True)
    return spec


def main() -> None:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU (jax.devices()[0].platform == "
            f"{devices[0].platform!r}); refusing to run on the CPU"
        )
    print(f"compile cache: {CACHE_DIR}", flush=True)
    jax.monitoring.register_event_time_span_listener(_on_span)
    timed("phase A", phase_a)
    timed("phase B", phase_b)
    timed("phase C", phase_c)
    peak = devices[0].memory_stats()["peak_bytes_in_use"]
    print(f"peak_bytes_in_use: {peak}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))


if __name__ == "__main__":
    main()
