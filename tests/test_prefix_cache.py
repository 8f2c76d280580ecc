"""Prefix-cache + chunked-prefill subsystem: refcount/eviction invariants,
copy-on-write forking, preempt→evict→readmit equivalence, chunked-vs-one-shot
prefill equality across kv precisions, and cross-precision isolation."""
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
    PrefixCache,
    SamplingParams,
    ServeEngine,
    block_hashes,
)


def _cfg(**kw):
    base = dataclasses.replace(
        get_config("llama3.2-3b").reduced(),
        n_layers=2, d_model=64, d_ff=128, vocab=256, n_heads=4, n_kv_heads=2,
        head_dim=16, serve_kv_bits=8,
    )
    return dataclasses.replace(base, **kw)


@pytest.fixture(scope="module")
def setup():
    cfg = _cfg()
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _pool(cfg, num_pages=8, page_size=4, kv_bits=8):
    return PagedKVCache(cfg, num_pages=num_pages, page_size=page_size, kv_bits=kv_bits)


# ---------------------------------------------------- hash-chain + bookkeeping
def test_block_hash_chain_prefix_property():
    a = np.arange(32, dtype=np.int32)
    b = np.concatenate([np.arange(16, dtype=np.int32), 99 + np.arange(16, dtype=np.int32)])
    ha, hb = block_hashes(a, 8), block_hashes(b, 8)
    assert len(ha) == len(hb) == 4
    assert ha[:2] == hb[:2]  # shared 16-token prefix
    assert ha[2:] != hb[2:]  # divergence poisons every later block
    assert block_hashes(a, 8, ("w", 4)) != ha  # salt separates weight precisions
    assert block_hashes(a[:7], 8) == []  # partial blocks are not hashable


def test_refcount_sharing_and_release_to_lru():
    cfg = _cfg()
    pool = _pool(cfg)
    pc = PrefixCache(pool)
    h = block_hashes(np.arange(8, dtype=np.int32), 4)
    t0 = pool.allocate(0, 2)
    pc.register(h, t0)
    # a second request adopts both pages: refcount 2, still registered
    pool.allocate(1, 3, prefix_pages=tuple(t0))
    assert pool.refcount(t0[0]) == 2
    pool.free(0)
    assert pool.refcount(t0[0]) == 1  # rid 1 still holds them
    assert pc.num_retained == 0 and pool.num_free == 5
    pool.free(1)
    # last ref dropped: registered pages retained in LRU, the fresh page freed
    assert pc.num_retained == 2 and pool.num_free == 6
    assert pool.num_allocatable == 8
    # match serves the retained chain; adopting revives it out of the LRU
    assert pc.match(h) == t0
    pool.allocate(2, 2, prefix_pages=tuple(t0))
    pc.acquire_note(t0)
    assert pc.num_retained == 0 and pool.refcount(t0[0]) == 1


def test_lru_eviction_order_and_liveness():
    cfg = _cfg()
    pool = _pool(cfg, num_pages=4)
    pc = PrefixCache(pool)
    ha = block_hashes(np.arange(4, dtype=np.int32), 4)
    hb = block_hashes(100 + np.arange(4, dtype=np.int32), 4)
    pa = pool.allocate(0, 1)
    pc.register(ha, pa)
    pb = pool.allocate(1, 1)
    pc.register(hb, pb)
    pool.free(0)  # retained first -> LRU victim
    pool.free(1)
    assert pc.num_retained == 2 and pool.num_free == 2
    # allocating 3 pages reclaims the least-recently-used entry (ha) only
    pool.allocate(2, 3)
    assert pc.match(ha) == [] and pc.match(hb) == pb
    assert pc.stats.evictions == 1
    # a *live* registered page is never evicted: hb's page is re-adopted
    pool.allocate(3, 1, prefix_pages=tuple(pb))
    pc.acquire_note(pb)
    pool.free(2)
    pool.allocate(4, 3)  # needs every free page; must not touch live pb
    assert pc.match(hb) == pb
    assert pool.refcount(pb[0]) == 1


def test_copy_on_write_fork_leaves_original_intact():
    cfg = _cfg()
    pool = _pool(cfg)
    rng = np.random.default_rng(0)
    pool.allocate(0, 2)
    L, ps, hkv, hd = cfg.n_layers, 4, cfg.n_kv_heads, cfg.hd
    kq = rng.integers(-127, 128, (L, 8, hkv, hd)).astype(np.int8)
    ks = (rng.random((L, 8, hkv)) * 0.1).astype(np.float32)
    pool.write_prompt(0, jnp.asarray(kq), jnp.asarray(kq), jnp.asarray(ks), jnp.asarray(ks))
    orig = pool.table(0)
    # second request adopts both pages then forks the last one (divergence)
    pool.allocate(1, 2, prefix_pages=tuple(orig))
    new = pool.fork_page(1, 1)
    assert new not in orig and pool.table(1) == [orig[0], new]
    assert pool.refcount(orig[1]) == 1  # rid 0's reference only
    # the fork is payload-identical until someone writes it
    np.testing.assert_array_equal(
        np.asarray(pool.k[:, new]), np.asarray(pool.k[:, orig[1]])
    )
    # writing the fork leaves the original untouched
    tok = jnp.full((L, 1, hkv, hd), 7, jnp.int8)
    sc = jnp.ones((L, 1, hkv), jnp.float32)
    pool.write_token([1], np.array([7]), (tok, tok, sc, sc))
    np.testing.assert_array_equal(np.asarray(pool.k[:, orig[1], :, 3]), kq[:, 7])
    np.testing.assert_array_equal(
        np.asarray(pool.k[:, new, :, 3]), np.full((L, hkv, hd), 7, np.int8)
    )


# ------------------------------------------------------- engine-level reuse
def _run_engine(cfg, params, prompts, new_tokens=4, prefill_chunk=32, **submit_kw):
    eng = ServeEngine(
        cfg, params, max_slots=len(prompts), num_pages=64, page_size=4,
        prefill_chunk=prefill_chunk,
    )
    sampling = SamplingParams(max_new_tokens=new_tokens)
    precision = PrecisionParams(**submit_kw)
    reqs = [eng.submit(p, sampling, precision) for p in prompts]
    eng.run()
    return eng, reqs


@pytest.mark.parametrize("kv_bits", [4, 8, 16])
def test_chunked_equals_one_shot_prefill(setup, kv_bits):
    """Chunked prefill (chunk < prompt) must produce the same greedy tokens
    as a one-shot prefill (chunk >= prompt), for every kv precision."""
    cfg, params = setup
    w_bits = 16 if kv_bits == 16 else 8
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab, 19).astype(np.int32) for _ in range(2)]
    _, chunked = _run_engine(
        cfg, params, prompts, prefill_chunk=4, w_bits=w_bits, kv_bits=kv_bits
    )
    _, oneshot = _run_engine(
        cfg, params, prompts, prefill_chunk=64, w_bits=w_bits, kv_bits=kv_bits
    )
    assert [r.out_tokens for r in chunked] == [r.out_tokens for r in oneshot]


def test_chunked_prefill_matches_manual_decode_loop(setup):
    """Cold chunked prefill through the paged pool == the dense
    prefill + decode_step reference loop (greedy, bf16)."""
    cfg, params = setup
    cfg16 = dataclasses.replace(cfg, serve_kv_bits=16)
    prompt = np.arange(1, 14, dtype=np.int32)
    _, (req,) = _run_engine(
        cfg16, params, [prompt], new_tokens=4, prefill_chunk=4,
        w_bits=16, kv_bits=16,
    )
    logits, cache = T.prefill(params, {"tokens": jnp.asarray(prompt)[None]}, cfg16, 64)
    manual = []
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    for _ in range(4):
        manual.append(int(tok[0, 0]))
        logits, cache = T.decode_step(params, tok, cache, cfg16)
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    assert req.out_tokens == manual


@pytest.mark.parametrize("kv_bits", [4, 8, 16])
def test_warm_prefix_equals_cold_run(setup, kv_bits):
    """A warm-cache request (prefix pages adopted, only the suffix computed)
    must produce token-for-token the same greedy output as the identical
    request on a cold engine."""
    cfg, params = setup
    w_bits = 16 if kv_bits == 16 else 8
    rng = np.random.default_rng(6)
    sys_prompt = rng.integers(0, cfg.vocab, 16).astype(np.int32)
    tails = [rng.integers(0, cfg.vocab, 6).astype(np.int32) for _ in range(2)]
    prompts = [np.concatenate([sys_prompt, t]) for t in tails]

    eng = ServeEngine(cfg, params, max_slots=2, num_pages=64, page_size=4,
                      prefill_chunk=8)
    a = eng.submit(prompts[0], SamplingParams(max_new_tokens=5), PrecisionParams(w_bits=w_bits, kv_bits=kv_bits))
    eng.run()
    b = eng.submit(prompts[1], SamplingParams(max_new_tokens=5), PrecisionParams(w_bits=w_bits, kv_bits=kv_bits))
    eng.run()
    assert eng.stats.prefix_hit_tokens >= 16  # b adopted the shared prefix

    for i, warm in enumerate((a, b)):
        cold_eng = ServeEngine(cfg, params, max_slots=1, num_pages=64,
                               page_size=4, prefill_chunk=8,
                               enable_prefix_cache=False)
        cold = cold_eng.submit(prompts[i], SamplingParams(max_new_tokens=5), PrecisionParams(w_bits=w_bits, kv_bits=kv_bits))
        cold_eng.run()
        assert warm.out_tokens == cold.out_tokens, f"request {i} (kv{kv_bits})"


def test_full_prompt_hit_forks_divergence_page(setup):
    """Identical prompt twice, prompt length an exact page multiple: the
    second request hits every block, is capped at plen-1, CoW-forks the last
    page, and still produces identical tokens."""
    cfg, params = setup
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, cfg.vocab, 16).astype(np.int32)  # 4 pages of 4
    eng = ServeEngine(cfg, params, max_slots=1, num_pages=32, page_size=4,
                      prefill_chunk=8)
    a = eng.submit(prompt, SamplingParams(max_new_tokens=4), PrecisionParams(w_bits=8, kv_bits=8))
    eng.run()
    b = eng.submit(prompt, SamplingParams(max_new_tokens=4), PrecisionParams(w_bits=8, kv_bits=8))
    eng.run()
    pc = eng.prefix_cache_for(8)
    assert pc.stats.forks >= 1
    assert a.out_tokens == b.out_tokens
    # 15 of 16 prompt tokens served from cache on the second admission
    assert eng.stats.prefix_hit_tokens == 15


def test_full_pool_degrades_hit_instead_of_stalling(setup):
    """A capped (mid-page) hit needs one transient fork page; when the pool
    is entirely the request's own cached chain, admission must degrade to
    the floored no-fork hit instead of failing forever."""
    from repro.serve import ServeRequest

    cfg, params = setup
    eng = ServeEngine(cfg, params, max_slots=2, num_pages=2, page_size=4,
                      prefill_chunk=16)
    cache = eng.cache_for(8)
    pc = eng.prefix_cache_for(8)
    prompt = np.arange(8, dtype=np.int32)  # exactly 2 blocks
    hashes = block_hashes(prompt, 4, ("w", 8))
    pages = cache.allocate(0, 2)
    pc.register(hashes, pages)
    cache.free(0)  # whole pool = this chain, retained, zero free pages
    req = ServeRequest(rid=1, prompt=prompt, max_new_tokens=1,
                       w_bits=8, kv_bits=8)
    # capped hit (7 tokens) would need 2 shared + 1 fork page = impossible;
    # the cascade lands on the floored 1-block hit, evicting the tail block
    assert eng._try_admit(req)
    assert req.cache_len == 4
    assert cache.table(1)[0] == pages[0]  # head block adopted
    assert pc.match(hashes) == pages[:1]  # tail block was evicted


def test_preempt_evict_readmit_matches_uncached_run(setup):
    """Preemption releases pages into the prefix cache; readmission resumes
    from the still-cached blocks (recompute only what was evicted) and the
    final tokens equal an engine with caching disabled."""
    cfg, params = setup
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, cfg.vocab, 10).astype(np.int32) for _ in range(3)]

    def run(enable):
        eng = ServeEngine(cfg, params, max_slots=3, num_pages=10, page_size=4,
                          prefill_chunk=16, enable_prefix_cache=enable)
        reqs = [eng.submit(p, SamplingParams(max_new_tokens=8), PrecisionParams(w_bits=8, kv_bits=8)) for p in prompts]
        eng.run()
        return eng, reqs

    warm_eng, warm = run(True)
    cold_eng, cold = run(False)
    assert warm_eng.stats.preemptions > 0 and cold_eng.stats.preemptions > 0
    assert all(len(r.out_tokens) == 8 for r in warm)
    assert [r.out_tokens for r in warm] == [r.out_tokens for r in cold]


def test_preempt_resumes_from_cached_pages(setup):
    """A preempted request's materialized blocks are released *into* the
    prefix cache; readmission adopts the surviving chain (prompt AND
    generated-token blocks) instead of re-prefilling from scratch, and the
    continuation equals an undisturbed run."""
    cfg, params = setup
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, cfg.vocab, 12).astype(np.int32)

    eng = ServeEngine(cfg, params, max_slots=1, num_pages=32, page_size=4,
                      prefill_chunk=16)
    req = eng.submit(prompt, SamplingParams(max_new_tokens=8), PrecisionParams(w_bits=8, kv_bits=8))
    for _ in range(5):  # prefill + a few decode steps
        eng.step()
    assert len(req.out_tokens) >= 4
    hits_before = eng.stats.prefix_hit_tokens
    eng._preempt(req)  # deterministic mid-decode eviction
    eng.run()
    assert req.done and len(req.out_tokens) == 8 and req.preemptions == 1
    # readmission hit the feed chain (prompt + generated tokens, sans the
    # capped divergence token) rather than recomputing it
    assert eng.stats.prefix_hit_tokens - hits_before >= 12

    undisturbed = ServeEngine(cfg, params, max_slots=1, num_pages=32,
                              page_size=4, prefill_chunk=16,
                              enable_prefix_cache=False)
    ref = undisturbed.submit(prompt, SamplingParams(max_new_tokens=8), PrecisionParams(w_bits=8, kv_bits=8))
    undisturbed.run()
    assert req.out_tokens == ref.out_tokens


def test_cross_precision_isolation(setup):
    """A bf16 request must not hit int8 prefix pages (separate pools), and a
    W4 request must not hit W8-written pages (hash-chain salt)."""
    cfg, params = setup
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, cfg.vocab, 12).astype(np.int32)
    eng = ServeEngine(cfg, params, max_slots=1, num_pages=64, page_size=4,
                      prefill_chunk=16)
    eng.submit(prompt, SamplingParams(max_new_tokens=2), PrecisionParams(w_bits=8, kv_bits=8))
    eng.run()
    hits0 = eng.stats.prefix_hit_tokens
    # same tokens, bf16 KV: different pool, no hit possible
    eng.submit(prompt, SamplingParams(max_new_tokens=2), PrecisionParams(w_bits=16, kv_bits=16))
    eng.run()
    assert eng.stats.prefix_hit_tokens == hits0
    # same tokens, same kv pool, different weight precision: salt separates
    eng.submit(prompt, SamplingParams(max_new_tokens=2), PrecisionParams(w_bits=4, kv_bits=8))
    eng.run()
    assert eng.stats.prefix_hit_tokens == hits0
    # and the same (w, kv) choice *does* hit
    eng.submit(prompt, SamplingParams(max_new_tokens=2), PrecisionParams(w_bits=8, kv_bits=8))
    eng.run()
    assert eng.stats.prefix_hit_tokens > hits0


def test_interleaved_prefill_does_not_stall_decode(setup):
    """A long prompt admitted mid-stream prefills in chunks while the running
    request keeps decoding (no full-prompt stall)."""
    cfg, params = setup
    rng = np.random.default_rng(10)
    eng = ServeEngine(cfg, params, max_slots=2, num_pages=64, page_size=4,
                      prefill_chunk=4)
    a = eng.submit(rng.integers(0, cfg.vocab, 4).astype(np.int32), SamplingParams(max_new_tokens=12), PrecisionParams(w_bits=8))
    eng.step()
    before = len(a.out_tokens)
    b = eng.submit(rng.integers(0, cfg.vocab, 24).astype(np.int32), SamplingParams(max_new_tokens=2), PrecisionParams(w_bits=8))
    eng.step()  # b prefills its first chunk only...
    assert 0 < b.cache_len < 24
    assert len(a.out_tokens) > before  # ...while a decoded in the same step
    eng.run()
    assert a.done and b.done and len(b.out_tokens) == 2
