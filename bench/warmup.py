"""Warm every step program a cell's traffic can call, through the public API.

The engine buckets a decode call by its rows and its page-table width, and a
chunk call by its rows, its chunk length and its width.  For each precision
and sampling variant of the mix and each table width its contexts span, the
sweep submits batches of requests that share a cached prefix, so that one
engine step runs a chunk call of a chosen tail length and a decode call of a
chosen batch at that width:

* tail = ``prefill_chunk`` with 1..``max_slots`` rows: every batch a decode
  call or a full chunk can have;
* each shorter power-of-two tail with 1 and 2 rows: the last chunk of a long
  prompt, which rarely coincides with another of the same length.
"""
from __future__ import annotations

import numpy as np

from bench.traffic import context_range, variants


def _pow2_upto(n: int) -> list[int]:
    out, p = [], 1
    while p < n:
        out.append(p)
        p *= 2
    return out + [p]


def widths(mix: dict, page_size: int) -> list[int]:
    lo, hi = context_range(mix)
    first = _pow2_upto(-(-lo // page_size))[-1]
    return [w for w in _pow2_upto(-(-hi // page_size)) if w >= first]


def sweep(engine, mix: dict, vocab: int, rng: np.random.Generator,
          serve_as: dict | None = None) -> int:
    """Run the sweep; returns the number of engine steps it took.
    ``serve_as`` maps a stated (w_bits, kv_bits) to the pair served."""
    from repro.serve.params import PrecisionParams, SamplingParams

    eng = mix["engine"]
    ps, chunk, slots = eng["page_size"], eng["prefill_chunk"], eng["max_slots"]
    tails = [(chunk, range(1, slots + 1))] + [
        (t, (1, 2)) for t in _pow2_upto(chunk)[:-1]
    ]
    steps = 0

    def run(reqs) -> None:
        nonlocal steps
        while not all(r.done or r.failed for r in reqs):
            engine.step()
            steps += 1
        bad = [r.error for r in reqs if r.failed]
        if bad:
            raise RuntimeError(f"warm-up request failed: {bad[0]}")

    # one cached prefix per precision pair, long enough for the widest table;
    # a shorter width takes its leading blocks (the block chain is the same)
    pairs = sorted({(v["w_bits"], v["kv_bits"]) for v in variants(mix)})
    widest = widths(mix, ps)[-1]
    bases = {pair: rng.integers(0, vocab, ps * (widest - 1), dtype=np.int32)
             for pair in pairs}

    def submit(v, prompt, max_new):
        samp = SamplingParams(
            temperature=v["temperature"], top_p=v["top_p"],
            seed=int(rng.integers(0, 2**31)), max_new_tokens=max_new,
        )
        stated = (v["w_bits"], v["kv_bits"])
        w_bits, kv_bits = (serve_as or {}).get(stated, stated)
        return engine.submit(prompt, samp, PrecisionParams(w_bits=w_bits, kv_bits=kv_bits))

    greedy = {"temperature": 0.0, "top_p": 1.0}
    run([  # all prefixes fill side by side, one chunk call per pair a step
        submit({"w_bits": w, "kv_bits": kv, **greedy},
               np.concatenate([bases[(w, kv)], bases[(w, kv)][:1]]), 1)
        for w, kv in pairs
    ])
    for v in variants(mix):
        base = bases[(v["w_bits"], v["kv_bits"])]
        for w in widths(mix, ps):
            for tail, batches in tails:
                cached = ps * (w - -(-(tail + 2) // ps))
                if cached < 0:
                    continue
                for b in batches:
                    run([
                        submit(v, np.concatenate([
                            base[:cached],
                            rng.integers(0, vocab, tail, dtype=np.int32),
                        ]), 2)
                        for _ in range(b)
                    ])
    return steps
