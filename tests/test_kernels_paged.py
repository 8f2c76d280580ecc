"""Paged flash-decode kernel vs oracles: page-table indirection, quantized
pools (int8/int4/bf16), ragged lengths (0 / page-boundary / full-table), the
fused new-token term, and append-then-attend round trips."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ops, ref
from repro.kernels.mqa_decode import mqa_decode_pallas
from repro.quant.pack import pack_int4
from repro.serve.kv_cache import PagedKVCache

RNG = np.random.default_rng(0)


def _case(b, n_layers, n_pages, ps, w, hkv, groups, d, kv_bits):
    """Random head-major pool + shuffled page tables + this step's token."""
    h = hkv * groups
    q = jnp.asarray(RNG.normal(size=(b, h, d)), jnp.float32)
    if kv_bits < 16:
        lim = 8 if kv_bits == 4 else 128
        kp = RNG.integers(-lim, lim, (n_layers, n_pages, hkv, ps, d)).astype(np.int8)
        vp = RNG.integers(-lim, lim, (n_layers, n_pages, hkv, ps, d)).astype(np.int8)
        ks = (RNG.random((n_layers, n_pages, hkv, ps)) * 0.1).astype(np.float32)
        vs = (RNG.random((n_layers, n_pages, hkv, ps)) * 0.1).astype(np.float32)
        nk = RNG.integers(-lim, lim, (b, hkv, d)).astype(np.int8)
        nv = RNG.integers(-lim, lim, (b, hkv, d)).astype(np.int8)
        nks = (RNG.random((b, hkv)) * 0.1).astype(np.float32)
        nvs = (RNG.random((b, hkv)) * 0.1).astype(np.float32)
    else:
        kp = RNG.normal(size=(n_layers, n_pages, hkv, ps, d)).astype(np.float32)
        vp = RNG.normal(size=(n_layers, n_pages, hkv, ps, d)).astype(np.float32)
        ks = vs = nks = nvs = None
        nk = RNG.normal(size=(b, hkv, d)).astype(np.float32)
        nv = RNG.normal(size=(b, hkv, d)).astype(np.float32)
    # every row gets distinct pages, shuffled: the table indirection matters
    tables = RNG.permutation(n_pages)[: b * w].reshape(b, w).astype(np.int32)
    J = lambda x: None if x is None else jnp.asarray(x)
    return (
        q, J(kp), J(vp), J(ks), J(vs), jnp.asarray(tables),
        J(nk), J(nv), J(nks), J(nvs),
    )


def _packed(x, kv_bits):
    if x is None or kv_bits != 4:
        return x
    return pack_int4(x, axis=-1)


def _paged(case, lengths, layer, kv_bits, backend, window=None, interpret=None):
    q, kp, vp, ks, vs, tables, nk, nv, nks, nvs = case
    return ops.paged_mqa_decode(
        q, _packed(kp, kv_bits), _packed(vp, kv_bits), ks, vs, tables, lengths,
        layer, _packed(nk, kv_bits), _packed(nv, kv_bits), nks, nvs,
        kv_bits=kv_bits, window=window, backend=backend, interpret=interpret,
    )


def _oracle(case, lengths, layer, d, window=None):
    q, kp, vp, ks, vs, tables, nk, nv, nks, nvs = case
    return ref.paged_mqa_decode_ref(
        q, kp, vp, ks, vs, tables, lengths, layer, nk, nv, nks, nvs,
        sm_scale=1.0 / np.sqrt(d), window=window,
    )


@pytest.mark.parametrize("kv_bits", [8, 4, 16])
@pytest.mark.parametrize(
    "b,hkv,groups,d,ps,w",
    [
        (2, 2, 4, 64, 8, 4),
        (3, 1, 8, 32, 16, 3),  # MQA, non-pow2 batch/width
        (2, 4, 1, 64, 4, 5),  # MHA
    ],
)
def test_paged_matches_oracle(kv_bits, b, hkv, groups, d, ps, w):
    n_layers, n_pages = 2, b * w
    case = _case(b, n_layers, n_pages, ps, w, hkv, groups, d, kv_bits)
    s = w * ps
    # ragged: full-table, page-boundary, zero-length rows
    lengths = jnp.asarray([s, 2 * ps, 0][:b], jnp.int32)
    for layer in range(n_layers):
        exp = _oracle(case, lengths, layer, d)
        for backend, interp in (("xla", None), ("pallas", True)):
            got = _paged(case, lengths, layer, kv_bits, backend, interpret=interp)
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(exp), atol=3e-3, rtol=3e-3,
                err_msg=f"{backend} layer={layer}",
            )


def test_paged_matches_dense_reference():
    """Pool + table indirection == contiguous cache: gather the pages by
    table, insert the new token at its position, run the dense oracle."""
    b, hkv, groups, d, ps, w, kv_bits = 2, 2, 2, 32, 8, 4, 8
    case = _case(b, 1, b * w, ps, w, hkv, groups, d, kv_bits)
    q, kp, vp, ks, vs, tables, nk, nv, nks, nvs = case
    s = w * ps
    lengths = jnp.asarray([s - 5, ps], jnp.int32)
    got = _paged(case, lengths, 0, kv_bits, "xla")

    rows = np.arange(b)
    dense = lambda pool: ref.gather_pages(pool, tables)[0]  # [B, S, Hkv, ...]
    kd = dense(kp).at[rows, lengths].set(nk)
    vd = dense(vp).at[rows, lengths].set(nv)
    ksd = dense(ks).at[rows, lengths].set(nks)
    vsd = dense(vs).at[rows, lengths].set(nvs)
    exp = ref.mqa_decode_ref(
        q, kd, vd, ksd[..., None], vsd[..., None], lengths + 1,
        sm_scale=1.0 / np.sqrt(d),
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), atol=3e-3, rtol=3e-3)


def test_zero_length_attends_only_to_new_token():
    """lengths == 0: softmax spans exactly the fused new token, so the
    output is its dequantized V — and nothing is read from the pool."""
    b, hkv, groups, d, ps, w = 2, 2, 3, 32, 8, 4
    case = _case(b, 1, b * w, ps, w, hkv, groups, d, 8)
    q, kp, vp, ks, vs, tables, nk, nv, nks, nvs = case
    # poison the pool: it must not leak into a zero-length row
    case = (q, kp.at[:].set(127), vp.at[:].set(127), ks, vs, tables, nk, nv, nks, nvs)
    lengths = jnp.zeros((b,), jnp.int32)
    exp = (nv.astype(jnp.float32) * nvs[..., None]).astype(np.float32)  # [B, Hkv, D]
    exp = np.repeat(np.asarray(exp), groups, axis=1).reshape(b, hkv * groups, d)
    for backend, interp in (("xla", None), ("pallas", True)):
        got = _paged(case, lengths, 0, 8, backend, interpret=interp)
        np.testing.assert_allclose(np.asarray(got), exp, atol=1e-5, rtol=1e-5)


def test_window_masking_matches_oracle():
    b, hkv, groups, d, ps, w = 2, 2, 2, 32, 8, 4
    case = _case(b, 1, b * w, ps, w, hkv, groups, d, 8)
    lengths = jnp.asarray([w * ps - 1, 2 * ps], jnp.int32)
    for window in (5, ps, 2 * ps + 3):
        exp = _oracle(case, lengths, 0, d, window=window)
        for backend, interp in (("xla", None), ("pallas", True)):
            got = _paged(case, lengths, 0, 8, backend, window=window, interpret=interp)
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(exp), atol=3e-3, rtol=3e-3,
                err_msg=f"{backend} window={window}",
            )


def test_stale_pool_entries_beyond_length_are_dead():
    """Corrupting pages past each row's length must not change the output
    (the clamped index map may still *fetch* them, never *use* them)."""
    b, hkv, groups, d, ps, w = 2, 2, 2, 32, 8, 4
    case = _case(b, 1, b * w, ps, w, hkv, groups, d, 8)
    q, kp, vp, ks, vs, tables, nk, nv, nks, nvs = case
    lengths = jnp.asarray([ps + 3, 1], jnp.int32)
    # corrupt every position >= its row's length through the table view
    kp2 = np.asarray(kp).copy()
    for row in range(b):
        ln = int(lengths[row])
        for pos in range(ln, w * ps):
            kp2[0, int(tables[row, pos // ps]), :, pos % ps] = 127
    case2 = (q, jnp.asarray(kp2), vp, ks, vs, tables, nk, nv, nks, nvs)
    for backend, interp in (("xla", None), ("pallas", True)):
        got = _paged(case, lengths, 0, 8, backend, interpret=interp)
        got2 = _paged(case2, lengths, 0, 8, backend, interpret=interp)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(got2), atol=1e-6, err_msg=backend
        )


def _tiny_cfg():
    return dataclasses.replace(
        get_config("llama3.2-3b").reduced(),
        n_layers=2, n_heads=4, n_kv_heads=2, head_dim=16, d_model=64,
    )


def test_append_then_attend_roundtrip():
    """Fused path (attend with new token, then scatter into the page) ==
    store-first path (write_token, then attend over the stored cache)."""
    cfg = _tiny_cfg()
    cache = PagedKVCache(cfg, num_pages=8, page_size=4, kv_bits=8)
    L, hkv, hd, ps = cfg.n_layers, cfg.n_kv_heads, cfg.hd, 4
    n_tok = 9  # mid-page: the append lands in an allocated page
    cache.allocate(0, 3)
    kq = RNG.integers(-127, 128, (L, 12, hkv, hd)).astype(np.int8)
    vq = RNG.integers(-127, 128, (L, 12, hkv, hd)).astype(np.int8)
    ks = (RNG.random((L, 12, hkv)) * 0.1).astype(np.float32)
    vs = (RNG.random((L, 12, hkv)) * 0.1).astype(np.float32)
    kq[:, n_tok:] = 0
    cache.write_prompt(0, jnp.asarray(kq), jnp.asarray(vq), jnp.asarray(ks), jnp.asarray(vs))

    q = jnp.asarray(RNG.normal(size=(1, cfg.n_heads, hd)), jnp.float32)
    nk = RNG.integers(-127, 128, (1, hkv, hd)).astype(np.int8)
    nv = RNG.integers(-127, 128, (1, hkv, hd)).astype(np.int8)
    nks = (RNG.random((1, hkv)) * 0.1).astype(np.float32)
    nvs = (RNG.random((1, hkv)) * 0.1).astype(np.float32)
    tables = cache.table_array([0], width=3)
    lengths = jnp.asarray([n_tok], jnp.int32)

    fused = {
        layer: _paged(
            (q, cache.k, cache.v, cache.k_scale, cache.v_scale, tables,
             jnp.asarray(nk), jnp.asarray(nv), jnp.asarray(nks), jnp.asarray(nvs)),
            lengths, layer, 8, "xla",
        )
        for layer in range(L)
    }

    # now store the token and attend over the updated pool with a zeroed
    # "new token" contribution excluded by comparing against the ref oracle
    per_layer_k = np.broadcast_to(nk[None], (L, 1, hkv, hd))
    per_layer_v = np.broadcast_to(nv[None], (L, 1, hkv, hd))
    per_layer_ks = np.broadcast_to(nks[None], (L, 1, hkv))
    per_layer_vs = np.broadcast_to(nvs[None], (L, 1, hkv))
    cache.write_token(
        [0], np.array([n_tok]),
        (jnp.asarray(per_layer_k), jnp.asarray(per_layer_v),
         jnp.asarray(per_layer_ks), jnp.asarray(per_layer_vs)),
    )
    gk = ref.gather_pages(cache.k, tables)
    gv = ref.gather_pages(cache.v, tables)
    gks = ref.gather_pages(cache.k_scale, tables)[..., None]
    gvs = ref.gather_pages(cache.v_scale, tables)[..., None]
    for layer in range(L):
        stored = ref.mqa_decode_ref(
            q, gk[layer], gv[layer], gks[layer], gvs[layer],
            lengths + 1, sm_scale=1.0 / np.sqrt(hd),
        )
        np.testing.assert_allclose(
            np.asarray(fused[layer]), np.asarray(stored), atol=3e-3, rtol=3e-3
        )


def test_decode_step_padding_rows_leave_pool_untouched():
    """pow2-bucket padding rows (valid=False) must not scatter into page 0."""
    import jax

    from repro.models import transformer as T
    from repro.serve.decode import paged_decode_step

    cfg = dataclasses.replace(_tiny_cfg(), vocab=64, serve_kv_bits=8)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    cache = PagedKVCache(cfg, num_pages=4, page_size=4, kv_bits=8)
    cache.allocate(0, 2)
    before = np.asarray(cache.k)
    tokens = jnp.zeros((2, 1), jnp.int32)
    lengths = jnp.asarray([2, 0], jnp.int32)
    tables = cache.table_array([0], width=2)
    tables = jnp.concatenate([tables, jnp.zeros_like(tables)], axis=0)
    valid = jnp.asarray([True, False])
    logits, pools = paged_decode_step(
        params, tokens, lengths, tables, valid,
        cache.k, cache.v, cache.k_scale, cache.v_scale, cfg=cfg,
    )
    assert logits.shape == (2, params["unembed"].shape[-1])
    after = np.asarray(pools[0])
    # row 0's token landed at page table(0)[0], offset 2
    page0 = cache.table(0)[0]
    assert not np.array_equal(after[:, page0, :, 2], before[:, page0, :, 2])
    # padding row wrote nowhere: pool page 0 offset 0 (its zero table entry)
    np.testing.assert_array_equal(after[:, 0, :, 0], before[:, 0, :, 0])


def test_mqa_decode_pallas_pads_non_multiple_widths():
    """The raw kernel no longer asserts s % bs == 0 — it pads and masks."""
    b, s, hkv, groups, d, bs = 2, 300, 2, 2, 64, 128
    h = hkv * groups
    q = jnp.asarray(RNG.normal(size=(b, hkv, groups, d)), jnp.float32)
    kd = jnp.asarray(RNG.integers(-127, 128, (b, s, hkv, d)), jnp.int8)
    vd = jnp.asarray(RNG.integers(-127, 128, (b, s, hkv, d)), jnp.int8)
    ks = jnp.asarray(RNG.random((b, s, hkv, 1)) * 0.1, jnp.float32)
    vs = jnp.asarray(RNG.random((b, s, hkv, 1)) * 0.1, jnp.float32)
    lengths = jnp.asarray([300, 123], jnp.int32)
    got = mqa_decode_pallas(
        q, kd, vd, ks, vs, lengths,
        kv_bits=8, sm_scale=1.0 / np.sqrt(d), bs=bs, interpret=True,
    )
    exp = ref.mqa_decode_ref(
        q.reshape(b, h, d), kd, vd, ks, vs, lengths, sm_scale=1.0 / np.sqrt(d)
    )
    np.testing.assert_allclose(
        np.asarray(got.reshape(b, h, d)), np.asarray(exp), atol=3e-3, rtol=3e-3
    )
