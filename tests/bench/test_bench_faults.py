"""A whole run with the timed path broken underneath comes out not correct:
once for each fault a one-chip serving cell can have.  The harness skips its
look for a chip; everything else is the benchmark's run at a tiny size."""
import time

import jax.numpy as jnp
import pytest

import tiny
from bench import harness
from repro.serve import decode as decode_mod
from repro.serve import engine as engine_mod
from repro.serve import prefill as prefill_mod
from test_bench_control import LIMITS


def _token_altered(monkeypatch):
    """Every decoded token is replaced by its neighbour where it is drawn."""
    orig = engine_mod.paged_decode_sample

    def altered(*args, **kw):
        tok, pools = orig(*args, **kw)
        return (tok + 1) % tiny.MODEL["vocab_size"], pools

    monkeypatch.setattr(engine_mod, "paged_decode_sample", altered)


def _state_unchanged(monkeypatch):
    """Prefill and decode steps hand back the KV pool they were given."""
    keep = lambda pools, *a, **kw: pools
    monkeypatch.setattr(decode_mod, "scatter_tokens", keep)
    monkeypatch.setattr(prefill_mod, "scatter_tokens", keep)


def _half_batch(monkeypatch):
    """A decode call computes the first half of its rows and hands their
    tokens to the second half."""
    orig = engine_mod.paged_decode_sample

    def half(params, tokens, lengths, tables, valid, *rest, **kw):
        tok, pools = orig(params, tokens, lengths, tables, valid, *rest, **kw)
        b = tok.shape[0]
        if b > 1:
            tok = jnp.concatenate([tok[: b // 2], tok[: b - b // 2]])
        return tok, pools

    monkeypatch.setattr(engine_mod, "paged_decode_sample", half)


@pytest.mark.parametrize(
    "fault", [_token_altered, _state_unchanged, _half_batch],
    ids=["token_altered", "state_unchanged", "half_batch"],
)
def test_a_broken_timed_path_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    engine_mod._shared_jits.cache_clear()  # new jits trace the broken path
    try:
        r = harness.run(
            tiny.plan(tiny.TENANTS_MIX, limits=LIMITS, per_group=3), seed=2**31 + 43,
            seconds=1.5, trace=False, t_start=time.perf_counter(),
            require_tpu=False, steps=tiny.WINDOW_STEPS,
        )
    finally:
        monkeypatch.undo()
        engine_mod._shared_jits.cache_clear()
    assert r["correct"] is False, r["info"]
    assert any(c["value"] is None or c["value"] > c["limit"] for c in r["checks"].values())
