"""Whether what the timed path served is correct.

Once the window has closed, a sample of the requests the engine finished is
drawn from the seed: in each precision group and kind (greedy or sampled)
the one that served the most tokens, and more at random.  The plain
reference (``reference.py``) runs once over each prompt with its served
tokens, at the precision the request states, and gives one number per group:

* ``gap_w<w>kv<kv>`` (greedy requests): the widest gap by which a served
  token's reference logit lies below the reference's best at its position;
* ``draw_w<w>kv<kv>`` (sampled requests): the widest distance by which the
  request's own uniform draw at a position lies outside the served token's
  interval of the reference's sampling distribution (``reference.draw_distance``).

Each number has its own limit in the cell's file
(``bench/cells/<workload>.json``), with the readings it was set from.  The
file's ``control`` maps a stated precision pair to the program's own
next-lower path, which the control run serves in its place.
"""
from __future__ import annotations

import json
import re
from pathlib import Path

import numpy as np

from bench import reference
from bench.weights import make_weights

CELLS = Path(__file__).with_name("cells")


def load_limits(workload: str) -> dict:
    return json.loads((CELLS / f"{workload}.json").read_text())


def _pair(name: str) -> tuple[int, int]:
    w, kv = re.fullmatch(r"w(\d+)kv(\d+)", name).groups()
    return int(w), int(kv)


def control_paths(limits: dict) -> dict[tuple, tuple]:
    """``{"w8kv8": "w4kv8"}`` in the cell's file as ``{(8, 8): (4, 8)}``."""
    return {_pair(k): _pair(v) for k, v in limits.get("control", {}).items()}


def number_name(w_bits: int, kv_bits: int, greedy: bool) -> str:
    return f"{'gap' if greedy else 'draw'}_w{w_bits}kv{kv_bits}"


def draw_sample(finished: list[dict], per_group: int, seed: int) -> list[dict]:
    """``finished``: dicts with prompt, served, w_bits, kv_bits, temperature,
    top_p and seed (the request's sampling seed)."""
    rng = np.random.default_rng([int(seed), 11])
    groups: dict[str, list[dict]] = {}
    for r in finished:
        name = number_name(r["w_bits"], r["kv_bits"], r["temperature"] == 0.0)
        groups.setdefault(name, []).append(r)
    sample = []
    for key in sorted(groups):
        rs = sorted(groups[key], key=lambda r: -len(r["served"]))
        sample.append(rs[0])
        rest = rs[1:]
        for i in rng.permutation(len(rest))[: per_group - 1]:
            sample.append(rest[i])
    return sample


def readings(model: dict, seed: int, sample: list[dict], seq_len: int | None = None,
             pos_len: int | None = None) -> dict[str, float]:
    """One number per group of ``sample`` (see the module's docstring).
    ``seq_len`` and ``pos_len``: the fixed lengths the reference pads every
    sequence and every run of served positions to (``reference.logits``)."""
    weights = make_weights(model, seed)
    out: dict[str, float] = {}
    by_precision: dict[tuple, list[dict]] = {}
    for r in sample:
        by_precision.setdefault((r["w_bits"], r["kv_bits"]), []).append(r)
    for (w_bits, kv_bits), rs in sorted(by_precision.items()):
        seqs = [np.concatenate([r["prompt"], r["served"][:-1]]).astype(np.int32) for r in rs]
        pos = [len(r["prompt"]) - 1 + np.arange(len(r["served"])) for r in rs]
        logits = reference.logits(weights, model, seqs, pos, w_bits, kv_bits,
                                  seq_len=seq_len, pos_len=pos_len)
        for r, lg in zip(rs, logits):
            served = np.asarray(r["served"])
            greedy = r["temperature"] == 0.0
            if greedy:
                far = lg.max(axis=-1) - lg[np.arange(len(served)), served]
            else:
                u = reference.uniforms(r["seed"], len(served))
                far = reference.draw_distance(lg, served, u, r["temperature"], r["top_p"])
            name = number_name(w_bits, kv_bits, greedy)
            out[name] = max(out.get(name, 0.0), float(far.max()))
    return out
