"""The plain reference against the program at a tiny size, on the CPU: the
program prefills a prompt through the paged cache, then decodes through it,
and its logits follow the reference's at every position."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from bench import modelcfg, reference
from bench.weights import make_weights
from repro.models import transformer as model_lib
from repro.serve.decode import paged_decode_step
from repro.serve.kv_cache import PagedKVCache
from repro.serve.prefill import chunk_prefill_step

PS, PAGES = 8, 16


def _program_logits(weights, m, tokens, n_prompt, w_bits, kv_bits):
    """Prefill tokens[:n_prompt] in one chunk, then decode the rest one by
    one, each step reading the pool the previous ones wrote."""
    cfg = dataclasses.replace(modelcfg.arch_config(m), serve_kv_bits=kv_bits)
    params = weights if w_bits == 16 else model_lib.quantize_params(weights, w_bits)
    cache = PagedKVCache(cfg, num_pages=PAGES, page_size=PS, kv_bits=kv_bits)
    table = np.asarray([cache.allocate(0, -(-len(tokens) // PS))], np.int32)
    pools = (cache.k, cache.v, cache.k_scale, cache.v_scale)
    chunk = jax.jit(chunk_prefill_step, static_argnames=("cfg",))
    decode = jax.jit(paged_decode_step, static_argnames=("cfg",))
    lg, pools = chunk(
        params, jnp.asarray(tokens[None, :n_prompt]), jnp.zeros(1, jnp.int32),
        jnp.full(1, n_prompt, jnp.int32), jnp.asarray(table), *pools, cfg=cfg,
    )
    out = [np.asarray(lg[0])]
    for pos in range(n_prompt, len(tokens)):
        lg, pools = decode(
            params, jnp.asarray(tokens[None, pos : pos + 1]),
            jnp.full(1, pos, jnp.int32), jnp.asarray(table),
            jnp.ones(1, bool), *pools, cfg=cfg,
        )
        out.append(np.asarray(lg[0]))
    return np.stack(out)


# grouped-query (tiny.MODEL: 4 query heads over 2 KV heads) and multi-head
# attention (as many KV heads as query heads: one query row per KV head)
MODELS = {"gqa": tiny.MODEL, "mha": {**tiny.MODEL, "num_key_value_heads": 4}}


@pytest.mark.parametrize("heads", sorted(MODELS))
@pytest.mark.parametrize("w_bits,kv_bits", [(16, 16), (16, 8), (8, 8), (4, 4)])
def test_reference_follows_prefill_then_decode_through_the_cache(w_bits, kv_bits, heads):
    m = MODELS[heads]
    weights = make_weights(m, 2**31 + 5)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, m["vocab_size"], 40).astype(np.int32)
    n_prompt = 29
    got = _program_logits(weights, m, tokens, n_prompt, w_bits, kv_bits)
    pos = np.arange(n_prompt - 1, len(tokens))
    want = reference.logits(weights, m, [tokens], [pos], w_bits, kv_bits)[0]
    assert got.shape == want.shape == (12, m["vocab_size"])
    # the program runs its activations in bf16 (8 significant bits) against
    # the reference's float32: logits of std ~1 agree to a few hundredths.
    # At kv4 a code step is 1/7 of a head's absmax, so bf16 noise in the keys
    # flips codes and the logits agree to a few tenths
    scale = float(np.std(want))
    err = float(np.max(np.abs(got - want)))
    assert err < (0.35 if kv_bits == 4 else 0.08) * scale, (err, scale)
    # and the same tokens come out on top almost everywhere
    assert np.mean(got.argmax(-1) == want.argmax(-1)) >= 0.9


def test_lower_weight_precision_moves_the_logits_past_that_tolerance():
    """The same comparison fails for the program one weight precision lower
    (W4 in place of W8), so the tolerance above would catch such a step."""
    m = tiny.MODEL
    weights = make_weights(m, 2**31 + 5)
    tokens = np.random.default_rng(0).integers(0, m["vocab_size"], 40).astype(np.int32)
    got = _program_logits(weights, m, tokens, 29, 4, 8)
    want = reference.logits(weights, m, [tokens], [np.arange(28, 40)], 8, 8)[0]
    assert float(np.max(np.abs(got - want))) > 0.08 * float(np.std(want))


@pytest.mark.parametrize("temperature,top_p", [(0.7, 0.9), (1.0, 1.0), (0.3, 0.5)])
def test_reference_replays_the_programs_sampled_draws(temperature, top_p):
    """Given the same logits, every token the program's sampler draws lies
    where the reference's uniform falls in its sampling CDF, and a token
    moved to its neighbour does not."""
    from repro.kernels import ops

    n, v = 64, 512
    logits = np.random.default_rng(3).normal(size=(n, v)).astype(np.float32)
    seed = 2**31 + 77
    keys = ops.sample_keys(np.full(n, seed, np.uint32), np.arange(n, dtype=np.int32))
    tokens = np.asarray(ops.sample_tokens(
        jnp.asarray(logits), keys, jnp.full(n, temperature, jnp.float32),
        None, jnp.full(n, top_p, jnp.float32),
    ))
    u = reference.uniforms(seed, n)
    far = reference.draw_distance(logits, tokens, u, temperature, top_p)
    assert far.max() < 1e-5, far.max()
    moved = reference.draw_distance(logits, (tokens + 1) % v, u, temperature, top_p)
    assert np.mean(moved > 0) > 0.9
