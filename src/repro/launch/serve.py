"""Serving launcher: continuous-batching multi-precision quantized inference
(the paper's use case at traffic).

    PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --reduced \
        --requests 8 --new-tokens 16 --precision-mix 4,8 --shared-prefix 64 \
        --temperature 0.8 --top-p 0.95 --seed 7

``--precision-mix`` assigns weight precisions to requests round-robin, so a
single engine decodes W4A16 and W8A16 requests in the same step (one batched
kernel call per precision group).  ``--w-bits`` forces one precision for all
requests (0 = arch default); ``--no-quantize`` serves raw bf16 weights.
``--shared-prefix N`` gives every request the same N-token system prompt:
the first request prefills it cold, every follower adopts the cached prefix
pages and prefills only its unique tail (see the prefix_* stats in the
output).  ``--prefill-chunk`` bounds per-step prefill work so long prompts
interleave with running decodes; ``--no-prefix-cache`` disables reuse.
``--spec-k K`` turns on self-speculative decoding: every request drafts up
to K tokens per round with the cheap ``--draft-bits`` weight set and
verifies them in one pass at its own precision (exact acceptance for greedy,
rejection sampling for sampled requests; see spec_* stats).

Sampling: ``--temperature`` (0 = greedy argmax, the default), ``--top-k``,
``--top-p`` and ``--seed`` build each request's ``SamplingParams``; request
``i`` uses ``seed + i``, so rerunning with the same seed reproduces every
stream exactly while distinct requests stay decorrelated.  ``--eos-id``
terminates a request the moment it emits that token instead of always
burning the full ``--new-tokens`` budget.

Requests are driven through the streaming ``ServeEngine.generate()`` API —
the JSON report includes per-request ``outputs`` (token prefixes) and
``finish_reasons`` collected from the stream.
"""
from __future__ import annotations

import argparse
import json
from typing import Optional


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--batch-size", type=int, default=4, help="concurrent slots")
    ap.add_argument("--page-size", type=int, default=16, help="KV page tokens")
    ap.add_argument("--w-bits", type=int, default=0, help="0 = arch default")
    ap.add_argument(
        "--precision-mix", default="",
        help="comma-separated w_bits cycled over requests, e.g. '4,8'",
    )
    ap.add_argument("--kv-bits", type=int, default=0, help="0 = arch default")
    ap.add_argument("--no-quantize", action="store_true")
    ap.add_argument(
        "--shared-prefix", type=int, default=0, metavar="N",
        help="first N prompt tokens shared by every request (system prompt); "
        "followers hit the prefix cache and prefill only their tails",
    )
    ap.add_argument(
        "--prefill-chunk", type=int, default=32,
        help="max prompt tokens prefilled per engine step (chunked prefill)",
    )
    ap.add_argument("--no-prefix-cache", action="store_true")
    ap.add_argument(
        "--spec-k", type=int, default=0, metavar="K",
        help="speculative draft tokens per round (0 = plain decode)",
    )
    ap.add_argument(
        "--draft-bits", type=int, default=4, choices=(4, 8, 16),
        help="weight precision of the speculative draft passes",
    )
    ap.add_argument(
        "--temperature", type=float, default=0.0,
        help="sampling temperature (0 = greedy argmax)",
    )
    ap.add_argument(
        "--top-k", type=int, default=0,
        help="keep only the k highest logits before sampling (0 = disabled)",
    )
    ap.add_argument(
        "--top-p", type=float, default=1.0,
        help="nucleus sampling mass (1.0 = disabled)",
    )
    ap.add_argument(
        "--seed", type=int, default=0,
        help="sampling seed; request i uses seed + i, so a rerun with the "
        "same seed reproduces every stream exactly",
    )
    ap.add_argument(
        "--eos-id", type=int, default=None,
        help="stop token id: requests finish on emitting it (default: none)",
    )
    return ap


def main(argv: Optional[list[str]] = None) -> dict:
    args = build_parser().parse_args(argv)

    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.models import transformer as model_lib
    from repro.serve import (
        GenerationOutput,
        PrecisionParams,
        SamplingParams,
        ServeEngine,
    )

    arch = get_config(args.arch)
    if args.reduced:
        arch = arch.reduced()

    if args.no_quantize:
        mix = [16]
    elif args.precision_mix:
        mix = [int(b) for b in args.precision_mix.split(",")]
    else:
        mix = [args.w_bits or arch.serve_w_bits]
    kv_bits = args.kv_bits or arch.serve_kv_bits
    if args.shared_prefix >= args.prompt_len:
        raise SystemExit("--shared-prefix must be < --prompt-len")

    # jitted: the f32 draws of each stacked leaf fuse into its bf16 cast
    # instead of materializing next to it (GBs per leaf at full width)
    params = jax.jit(model_lib.init_params, static_argnums=0)(
        arch, jax.random.PRNGKey(0)
    )
    max_len = args.prompt_len + args.new_tokens + arch.prefix_len + 8
    rng = np.random.default_rng(0)
    shared = rng.integers(0, arch.vocab, args.shared_prefix).astype(np.int32)

    def prompt() -> np.ndarray:
        tail = rng.integers(
            0, arch.vocab, args.prompt_len - args.shared_prefix
        ).astype(np.int32)
        return np.concatenate([shared, tail])

    if not ServeEngine.supports(arch):
        # recurrent-cache archs: static-wave fallback (single precision,
        # greedy-only) — refuse sampling flags rather than silently
        # reporting greedy results as sampled ones
        if args.temperature or args.top_k or args.top_p < 1.0 or args.seed:
            raise SystemExit(
                f"--temperature/--top-k/--top-p/--seed are not supported for "
                f"{arch.name} ({arch.family!r}): the static-wave fallback "
                "decodes greedily"
            )
        from repro.train.server import Request, Server

        srv = Server(
            arch, params, batch_size=args.batch_size, max_len=max_len,
            quantize=not args.no_quantize,
        )
        reqs = [
            Request(rid=i, prompt=prompt(), max_new_tokens=args.new_tokens)
            for i in range(args.requests)
        ]
        srv.serve(reqs)
        stats = srv.stats
        report = {
            "arch": arch.name,
            "scheduler": "static-wave (family not supported by paged engine)",
            "w_bits": arch.serve_w_bits if not args.no_quantize else 16,
            "requests": len(reqs),
            "tokens_out": stats.tokens_out,
            "prefill_s": round(stats.prefill_s, 3),
            "decode_s": round(stats.decode_s, 3),
            "decode_tok_per_s": round(
                stats.tokens_out / max(stats.decode_s, 1e-9), 1
            ),
            "outputs": [r.out_tokens[:16] for r in reqs],
        }
        print(json.dumps(report, indent=1))
        return report

    pages_per_slot = -(-max_len // args.page_size)
    engine = ServeEngine(
        arch, params,
        max_slots=args.batch_size,
        num_pages=args.batch_size * pages_per_slot,
        page_size=args.page_size,
        prefill_chunk=args.prefill_chunk,
        enable_prefix_cache=not args.no_prefix_cache,
        spec_k=args.spec_k,
        draft_bits=args.draft_bits,
    )
    reqs = [
        engine.submit(
            prompt(),
            SamplingParams(
                temperature=args.temperature,
                top_k=args.top_k,
                top_p=args.top_p,
                seed=args.seed + i,
                max_new_tokens=args.new_tokens,
                eos_id=args.eos_id,
            ),
            PrecisionParams(w_bits=mix[i % len(mix)], kv_bits=kv_bits),
        )
        for i in range(args.requests)
    ]
    # drive through the streaming API; the terminal outputs carry the streams
    outputs: dict[int, GenerationOutput] = {}
    stream_events = 0
    for ev in engine.generate(reqs):
        if isinstance(ev, GenerationOutput):
            outputs[ev.rid] = ev
        else:
            stream_events += 1
    stats = engine.stats
    ttfts = sorted(stats.ttfts)
    outs = [outputs[r.rid] for r in reqs]
    report = {
        "arch": arch.name,
        "w_bits_mix": mix,
        "kv_bits": kv_bits,
        "requests": len(reqs),
        "shared_prefix": args.shared_prefix,
        "temperature": args.temperature,
        "top_k": args.top_k,
        "top_p": args.top_p,
        "seed": args.seed,
        "tokens_out": stats.tokens_out,
        "stream_events": stream_events,
        "prefill_s": round(stats.prefill_s, 3),
        "prefill_chunks": stats.prefill_chunks,
        "decode_s": round(stats.decode_s, 3),
        "decode_tok_per_s": round(stats.decode_tok_per_s, 1),
        "ttft_ms_first": round(ttfts[0] * 1e3, 1) if ttfts else None,
        "ttft_ms_last": round(ttfts[-1] * 1e3, 1) if ttfts else None,
        "prefix_hit_rate": round(stats.prefix_hit_rate, 3),
        "prefix_hit_tokens": stats.prefix_hit_tokens,
        "decode_group_calls": {
            f"w{w}kv{k}": n for (w, k), n in stats.group_calls.items()
        },
        "mixed_precision_steps": stats.mixed_precision_steps,
        "mean_batch_occupancy": round(stats.mean_batch_occupancy, 2),
        "preemptions": stats.preemptions,
        "spec_k": args.spec_k,
        "spec_rounds": stats.spec_rounds,
        "spec_accept_rate": round(stats.spec_accept_rate, 3),
        "finish_reasons": [o.finish_reason for o in outs],
        "outputs": [list(o.tokens[:16]) for o in outs],
    }
    print(json.dumps(report, indent=1))
    return report


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
