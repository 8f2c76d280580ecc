"""One general generator for every traffic mix (``bench/traffic/<name>.json``).

Requests come in blocks of ``block`` requests, and an open-loop block spans
``block / rate_per_s`` seconds.  Every block draws its prompt and output
lengths at the same evenly spaced quantiles of the mix's distributions and
its inter-arrival gaps at the same quantiles of the exponential.  Which
tenant each size goes to depends on the block's index alone; the tenants'
counts in block ``b`` are the difference of the shares' largest-remainder
split of ``(b + 1) * block`` and ``b * block`` requests, so any run of blocks
holds the shares as closely as its size allows.  The seed orders the
requests and the gaps inside each block and picks every token id and
sampling seed.  A block's first arrival comes ``lead_s`` after its start and
its last ``lead_s`` or more before its end, so a window that spans whole
blocks holds the same requests for every seed, in another order.
"""
from __future__ import annotations

import itertools
import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Iterator, Optional

import numpy as np


@dataclass
class RequestSpec:
    index: int
    due: Optional[float]  # seconds after the schedule's origin; None: closed loop
    tenant: str
    prompt: np.ndarray  # int32 token ids (system prompt + user turn)
    system_len: int
    max_new_tokens: int
    w_bits: int
    kv_bits: int
    temperature: float
    top_p: float
    seed: int

    @property
    def greedy(self) -> bool:
        return self.temperature == 0.0


def quantile(dist: dict, u: float) -> int:
    """The ``u``-quantile of a length distribution, clipped to its bounds."""
    lo, hi = dist["min"], dist["max"]
    if dist["dist"] == "lognormal":
        x = dist["median"] * math.exp(dist["sigma"] * NormalDist().inv_cdf(u))
    elif dist["dist"] == "uniform":
        x = lo + u * (hi - lo)
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return int(min(max(round(x), lo), hi))


def _counts(shares: list[float], n: int) -> list[int]:
    """Largest-remainder split of ``n`` by ``shares``."""
    raw = [s * n / sum(shares) for s in shares]
    out = [int(r) for r in raw]
    for i in sorted(range(len(raw)), key=lambda i: out[i] - raw[i])[: n - sum(out)]:
        out[i] += 1
    return out


def _midpoints(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


class Traffic:
    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix = mix
        self.vocab = vocab
        self.rng = np.random.default_rng([int(seed), 7])
        self.tenants = mix["tenants"]
        self.block = int(mix["block"])
        arrival = mix.get("arrival", {})
        rate = arrival.get("rate_per_s")
        self.rate = float(rate) if rate else None
        self.lead = float(arrival.get("lead_s", 0.0))
        # system prompts: lengths evenly spaced from the shortest to the longest
        # bound over the tenants that have one, in the file's order; token ids
        # drawn from the seed
        with_sys = [t for t in self.tenants if t.get("system_prompt")]
        n_sys = len(with_sys)
        lengths = [
            round(lo + (hi - lo) * (i / (n_sys - 1) if n_sys > 1 else 0.5))
            for i, (lo, hi) in enumerate(t["system_prompt"] for t in with_sys)
        ]
        self.system = {
            t["name"]: self.rng.integers(0, vocab, n, dtype=np.int32)
            for t, n in zip(with_sys, lengths)
        }

    def _block_plan(self, b: int):
        """Block ``b``: its requests as (tenant index, prompt length, output
        length) in the seed's order, and their due times."""
        n = self.block
        u = _midpoints(n)
        prompt = [quantile(self.mix["prompt"], x) for x in u]
        output = [quantile(self.mix["output"], x) for x in u]
        shares = [t["share"] for t in self.tenants]
        tenant_idx = []
        for i, (hi, lo) in enumerate(zip(_counts(shares, n * (b + 1)), _counts(shares, n * b))):
            tenant_idx += [i] * (hi - lo)
        # the pairing of sizes and tenants is the same for every seed
        pair = np.random.default_rng([b, 13])
        reqs = list(zip(
            [tenant_idx[i] for i in pair.permutation(n)],
            prompt,
            [output[i] for i in pair.permutation(n)],
        ))
        reqs = [reqs[i] for i in self.rng.permutation(n)]
        if not self.rate:
            return reqs, [None] * n
        gaps = np.array([-math.log(1.0 - x) / self.rate for x in u])[self.rng.permutation(n)]
        span = n / self.rate - 2 * self.lead
        starts = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        return reqs, [float(b * n / self.rate + self.lead + span * s / gaps.sum())
                      for s in starts]

    def requests(self) -> Iterator[RequestSpec]:
        index = 0
        for b in itertools.count():
            reqs, dues = self._block_plan(b)
            for (ti, prompt_len, max_new), due in zip(reqs, dues):
                t = self.tenants[ti]
                sys_tokens = self.system.get(t["name"], np.zeros(0, np.int32))
                user = self.rng.integers(0, self.vocab, prompt_len, dtype=np.int32)
                yield RequestSpec(
                    index=index,
                    due=due,
                    tenant=t["name"],
                    prompt=np.concatenate([sys_tokens, user]),
                    system_len=len(sys_tokens),
                    max_new_tokens=max_new,
                    w_bits=int(t["w_bits"]),
                    kv_bits=int(t["kv_bits"]),
                    temperature=float(t.get("temperature", 0.0)),
                    top_p=float(t.get("top_p", 1.0)),
                    seed=int(self.rng.integers(0, 2**31)),
                )
                index += 1


def context_range(mix: dict) -> tuple[int, int]:
    """Shortest prompt and longest prompt + output any request can have."""
    sys = [t.get("system_prompt") or [0, 0] for t in mix["tenants"]]
    lo = min(s[0] for s in sys) + mix["prompt"]["min"]
    hi = max(s[1] for s in sys) + mix["prompt"]["max"] + mix["output"]["max"]
    return lo, hi


def variants(mix: dict) -> list[dict]:
    """Each (w_bits, kv_bits, sampling) combination a step can batch: a
    precision group's calls are greedy-only or carry sampling arrays."""
    unique = []
    for t in mix["tenants"]:
        v = {"w_bits": int(t["w_bits"]), "kv_bits": int(t["kv_bits"]),
             "temperature": float(t.get("temperature", 0.0)),
             "top_p": float(t.get("top_p", 1.0))}
        if v not in unique:
            unique.append(v)
    return unique
