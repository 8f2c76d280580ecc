"""Continuous-batching engine: scheduler invariants, paged KV cache reuse,
mixed-precision grouping, and batched-vs-sequential decode equivalence."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import transformer as T
from repro.serve import (
    PagedKVCache,
    PrecisionParams,
    RequestState,
    SamplingParams,
    Scheduler,
    ServeEngine,
    ServeRequest,
)


def _req(rid, arrival, prompt_len=8, max_new=4, w_bits=8, kv_bits=8):
    return ServeRequest(
        rid=rid,
        prompt=np.arange(prompt_len, dtype=np.int32),
        max_new_tokens=max_new,
        w_bits=w_bits,
        kv_bits=kv_bits,
        arrival=arrival,
    )


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(
        get_config("llama3.2-3b").reduced(),
        n_layers=2, d_model=64, d_ff=128, vocab=128, n_heads=2, n_kv_heads=2,
        head_dim=32, serve_kv_bits=16,
    )
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


# ------------------------------------------------------- scheduler invariants
def test_scheduler_capacity():
    """Running set never exceeds max_slots even with everything admissible."""
    sched = Scheduler(max_slots=2)
    for i in range(5):
        sched.submit(_req(i, i))
    admitted = sched.admit(lambda r: True)
    assert len(admitted) == 2
    assert len(sched.running) == 2
    assert sched.admit(lambda r: True) == []  # slots full
    sched.finish(sched.running[0])
    assert [r.rid for r in sched.admit(lambda r: True)] == [2]  # FCFS refill


def test_no_starvation_head_of_line():
    """A non-fitting head blocks younger requests from bypassing it."""
    sched = Scheduler(max_slots=4)
    big = _req(0, 0, prompt_len=100)
    small = _req(1, 1, prompt_len=2)
    sched.submit(big)
    sched.submit(small)
    admitted = sched.admit(lambda r: len(r.prompt) <= 10)
    assert admitted == []  # small never jumps the queue
    admitted = sched.admit(lambda r: True)
    assert [r.rid for r in admitted] == [0, 1]  # arrival order preserved


def test_preempt_requeues_in_arrival_order():
    sched = Scheduler(max_slots=3)
    for i in range(3):
        sched.submit(_req(i, i))
    sched.admit(lambda r: True)
    victim = sched.pick_victim()
    assert victim.arrival == 2  # youngest
    sched.preempt(victim)
    assert victim.state is RequestState.WAITING
    assert victim.preemptions == 1
    sched.submit(_req(9, 9))
    # preempted (arrival 2) sits ahead of the newer arrival 9
    assert [r.arrival for r in sched.waiting] == [2, 9]


# ----------------------------------------------------------- paged KV cache
def _tiny_cache(**kw):
    cfg = dataclasses.replace(
        get_config("llama3.2-3b").reduced(), n_layers=2, n_kv_heads=2, head_dim=8
    )
    defaults = dict(num_pages=4, page_size=4, kv_bits=8)
    defaults.update(kw)
    return PagedKVCache(cfg, **defaults)


def test_kv_page_capacity_and_reuse():
    cache = _tiny_cache()
    a = cache.allocate(0, 3)
    assert not cache.can_allocate(2)
    with pytest.raises(MemoryError):
        cache.allocate(1, 2)
    cache.free(0)
    b = cache.allocate(1, 3)
    assert b == a  # LIFO free list: freed pages reused immediately
    assert cache.stats().high_water == 3


def test_kv_write_gather_roundtrip():
    """Prompt scatter + per-token scatter land at the right positions."""
    cache = _tiny_cache(kv_bits=16)
    L, ps = 2, 4
    hkv, hd = cache.k.shape[2], cache.k.shape[4]
    cache.allocate(7, 2)
    row = jnp.arange(L * 2 * ps * hkv * hd, dtype=jnp.float32).reshape(
        L, 2 * ps, hkv, hd
    )
    cache.write_prompt(7, row, row * 2)
    tok_k = jnp.full((L, 1, hkv, hd), -1.0)
    cache.write_token([7], np.array([5]), (tok_k, tok_k))
    table = jnp.asarray(cache.table(7), jnp.int32)
    got = jnp.swapaxes(cache.k[:, table], 2, 3).reshape(L, 2 * ps, hkv, hd)
    expect = row.astype(got.dtype).at[:, 5].set(-1.0)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(expect))


# ------------------------------------------------- engine: precision grouping
def test_mixed_precision_grouping(setup):
    cfg, params = setup
    cfg = dataclasses.replace(cfg, serve_kv_bits=8)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, 8).astype(np.int32) for _ in range(4)]

    eng = ServeEngine(cfg, params, max_slots=4, num_pages=32, page_size=8)
    mixed = [
        eng.submit(p, SamplingParams(max_new_tokens=5), PrecisionParams(w_bits=4 if i % 2 else 8, kv_bits=8))
        for i, p in enumerate(prompts)
    ]
    eng.run()
    assert all(r.done and len(r.out_tokens) == 5 for r in mixed)
    assert eng.stats.mixed_precision_steps > 0  # W4 and W8 decoded in one step
    assert set(eng.stats.group_calls) == {(4, 8), (8, 8)}

    # each group's tokens match a single-precision engine run
    for bits in (4, 8):
        solo_eng = ServeEngine(cfg, params, max_slots=4, num_pages=32, page_size=8)
        solo = [
            solo_eng.submit(p, SamplingParams(max_new_tokens=5), PrecisionParams(w_bits=bits, kv_bits=8))
            for i, p in enumerate(prompts)
            if (4 if i % 2 else 8) == bits
        ]
        solo_eng.run()
        mixed_same = [r for i, r in enumerate(mixed) if (4 if i % 2 else 8) == bits]
        assert [r.out_tokens for r in solo] == [r.out_tokens for r in mixed_same]


# --------------------------------------- batched vs sequential vs manual loop
def test_batched_equals_sequential(setup):
    cfg, params = setup
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab, 8).astype(np.int32) for _ in range(3)]

    batched = ServeEngine(cfg, params, max_slots=3, num_pages=24, page_size=8)
    br = [batched.submit(p, SamplingParams(max_new_tokens=4), PrecisionParams(w_bits=16, kv_bits=16)) for p in prompts]
    batched.run()

    seq_tokens = []
    for p in prompts:
        eng = ServeEngine(cfg, params, max_slots=1, num_pages=8, page_size=8)
        r = eng.submit(p, SamplingParams(max_new_tokens=4), PrecisionParams(w_bits=16, kv_bits=16))
        eng.run()
        seq_tokens.append(r.out_tokens)
    assert [r.out_tokens for r in br] == seq_tokens


def test_engine_matches_manual_decode_loop(setup):
    """Paged ragged decode == models.transformer prefill + decode_step."""
    cfg, params = setup
    prompt = np.arange(1, 9, dtype=np.int32)
    eng = ServeEngine(cfg, params, max_slots=1, num_pages=8, page_size=8)
    req = eng.submit(prompt, SamplingParams(max_new_tokens=4), PrecisionParams(w_bits=16, kv_bits=16))
    eng.run()

    logits, cache = T.prefill(params, {"tokens": jnp.asarray(prompt)[None]}, cfg, 64)
    manual = []
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    for _ in range(4):
        manual.append(int(tok[0, 0]))
        logits, cache = T.decode_step(params, tok, cache, cfg)
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    assert req.out_tokens == manual


def test_paged_gather_matches_ref_oracle(setup):
    """The paged layout feeds attention the same values as a dense cache:
    gathered pages through the kernel wrapper == kernels/ref.py oracle."""
    from repro.kernels import ops, ref

    cfg, _ = setup
    cache = PagedKVCache(cfg, num_pages=6, page_size=4, kv_bits=8)
    L, hkv, hd = cfg.n_layers, cfg.n_kv_heads, cfg.hd
    s = 12  # 3 pages
    rng = np.random.default_rng(2)
    kq = rng.integers(-127, 128, (L, s, hkv, hd)).astype(np.int8)
    vq = rng.integers(-127, 128, (L, s, hkv, hd)).astype(np.int8)
    ks = rng.random((L, s, hkv)).astype(np.float32) * 0.1
    vs = rng.random((L, s, hkv)).astype(np.float32) * 0.1
    cache.allocate(0, 3)
    cache.write_prompt(0, jnp.asarray(kq), jnp.asarray(vq), jnp.asarray(ks), jnp.asarray(vs))

    tables = cache.table_array([0], width=4)  # padded wider than needed
    gk = ref.gather_pages(cache.k, tables)
    gv = ref.gather_pages(cache.v, tables)
    gks = ref.gather_pages(cache.k_scale, tables)[..., None]
    gvs = ref.gather_pages(cache.v_scale, tables)[..., None]

    q = jnp.asarray(rng.standard_normal((1, cfg.n_heads, hd)), jnp.float32)
    lengths = jnp.asarray([10], jnp.int32)  # ragged: shorter than stored
    layer = 0
    got = ops.mqa_decode(
        q, gk[layer], gv[layer], gks[layer], gvs[layer], lengths, kv_bits=8, bs=8
    )
    want = ref.mqa_decode_ref(
        q, gk[layer], gv[layer], gks[layer], gvs[layer], lengths,
        sm_scale=1.0 / np.sqrt(hd),
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-2, atol=2e-2)
    # and the gather itself reproduced the dense rows
    np.testing.assert_array_equal(np.asarray(gk[:, 0, :s]), kq)


# ------------------------------------------------------ preemption + refill
def test_preemption_recovers(setup):
    """Pool too small for all requests: youngest gets preempted, everyone
    still finishes with a full token budget."""
    cfg, params = setup
    cfg = dataclasses.replace(cfg, serve_kv_bits=8)
    rng = np.random.default_rng(3)
    eng = ServeEngine(cfg, params, max_slots=3, num_pages=4, page_size=4)
    reqs = [
        eng.submit(rng.integers(0, cfg.vocab, 6).astype(np.int32), SamplingParams(max_new_tokens=8), PrecisionParams(w_bits=8))
        for _ in range(3)
    ]
    eng.run()
    assert all(r.done and len(r.out_tokens) == 8 for r in reqs)
    assert eng.stats.preemptions > 0
    # every page is reclaimable again: free, or retained (refcount 0) by the
    # prefix cache for future hits
    cache = eng.cache_for(8)
    assert cache.num_allocatable == 4
    assert not cache._tables and not cache._refcount


def test_continuous_refill(setup):
    """More requests than slots: finished slots refill without wave barriers
    and capacity is respected throughout."""
    cfg, params = setup
    rng = np.random.default_rng(4)
    eng = ServeEngine(cfg, params, max_slots=2, num_pages=16, page_size=8)
    reqs = [
        eng.submit(rng.integers(0, cfg.vocab, 8).astype(np.int32), SamplingParams(max_new_tokens=3 + i), PrecisionParams(w_bits=16))
        for i in range(5)
    ]
    while eng._sched.has_work():
        assert len(eng._sched.running) <= 2
        eng.step()
    assert all(r.done and len(r.out_tokens) == 3 + i for i, r in enumerate(reqs))
