"""The traffic generator: seeded, clipped, shares held, same work per seed."""
import collections
import itertools
import json
from pathlib import Path

import numpy as np
import pytest

from bench.traffic import Traffic, context_range, quantile, variants

TRAFFIC = Path(__file__).resolve().parents[2] / "bench" / "traffic"
MIXES = sorted(p.stem for p in TRAFFIC.glob("*.json"))


def _take(name, seed, n):
    mix = json.loads((TRAFFIC / f"{name}.json").read_text())
    return mix, list(itertools.islice(Traffic(mix, seed, 64000).requests(), n))


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    _, a = _take(name, 2**31 + 12345, 50)
    _, b = _take(name, 2**31 + 12345, 50)
    for x, y in zip(a, b):
        assert x.due == y.due and x.max_new_tokens == y.max_new_tokens
        assert x.tenant == y.tenant and x.seed == y.seed
        np.testing.assert_array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("name", MIXES)
def test_other_seed_same_sizes_in_another_order(name):
    mix = json.loads((TRAFFIC / f"{name}.json").read_text())
    n = mix["block"]
    _, a = _take(name, 1, n)
    _, b = _take(name, 2, n)
    assert sorted(len(r.prompt) - r.system_len for r in a) == sorted(
        len(r.prompt) - r.system_len for r in b)
    assert sorted(r.max_new_tokens for r in a) == sorted(r.max_new_tokens for r in b)
    assert [r.max_new_tokens for r in a] != [r.max_new_tokens for r in b]
    if a[0].due is not None:
        lead = mix["arrival"].get("lead_s", 0.0)
        span = n / mix["arrival"]["rate_per_s"]
        for x in (a, b):  # the block's first arrival at its lead, the last inside it
            assert x[0].due == pytest.approx(lead)
            assert all(lead <= r.due <= span - lead for r in x)


@pytest.mark.parametrize("name", MIXES)
def test_a_window_of_whole_blocks_holds_the_same_requests_for_every_seed(name):
    mix = json.loads((TRAFFIC / f"{name}.json").read_text())
    n = mix["block"]

    def window(seed):
        _, reqs = _take(name, seed, 4 * n)
        inside = reqs[n:3 * n]
        if reqs[0].due is not None:
            span = n / mix["arrival"]["rate_per_s"]
            inside = [r for r in reqs if span <= r.due < 3 * span]
        return sorted((r.tenant, len(r.prompt), r.max_new_tokens) for r in inside)

    assert len(window(2**31 + 5)) == 2 * n
    assert window(2**31 + 5) == window(3) == window(2**32 + 17)


@pytest.mark.parametrize("name", MIXES)
def test_lengths_respect_their_clips(name):
    mix, reqs = _take(name, 77, 400)
    for r in reqs:
        user = len(r.prompt) - r.system_len
        assert mix["prompt"]["min"] <= user <= mix["prompt"]["max"]
        assert mix["output"]["min"] <= r.max_new_tokens <= mix["output"]["max"]
        lo, hi = context_range(mix)
        assert lo <= len(r.prompt) and len(r.prompt) + r.max_new_tokens <= hi


def test_tenant_shares_and_system_prompts_hold():
    mix, reqs = _take("tenants.poisson", 5, 4 * 200)
    counts = collections.Counter(r.tenant for r in reqs)
    for t in mix["tenants"]:
        assert counts[t["name"]] == round(t["share"] * len(reqs))
    sys_lens = {r.tenant: r.system_len for r in reqs}
    assert sorted(sys_lens.values()) == [512, 768, 1024]
    b = [r for r in reqs if r.tenant == "B"]
    assert all(r.temperature == 0.7 and r.top_p == 0.9 and not r.greedy for r in b)
    a = [r for r in reqs if r.tenant == "A"]
    assert all(r.greedy and (r.w_bits, r.kv_bits) == (4, 4) for r in a)


def test_poisson_gaps_have_the_stated_rate():
    mix, reqs = _take("tenants.poisson", 9, 200)
    rate = mix["arrival"]["rate_per_s"]
    assert reqs[-1].due == pytest.approx(200 / rate, rel=0.05)
    n, lead = mix["block"], mix["arrival"]["lead_s"]
    for k in range(0, 200, n):  # every block spans block / rate seconds
        assert reqs[k].due == pytest.approx(k / rate + lead)
    dues = [r.due for r in reqs]
    assert dues == sorted(dues)


def test_quantiles_and_variants():
    logn = {"dist": "lognormal", "median": 128, "sigma": 0.7, "min": 32, "max": 512}
    assert quantile(logn, 0.5) == 128
    assert quantile(logn, 1e-9) == 32 and quantile(logn, 1 - 1e-9) == 512
    assert quantile({"dist": "uniform", "min": 10, "max": 20}, 0.5) == 15
    mix = json.loads((TRAFFIC / "tenants.poisson.json").read_text())
    got = {(v["w_bits"], v["kv_bits"], v["temperature"] > 0) for v in variants(mix)}
    assert got == {(4, 4, False), (8, 8, True), (16, 8, False)}
