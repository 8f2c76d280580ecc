"""Paged chunk-prefill kernel vs oracle: page-table indirection, quantized
pools (int8/int4/bf16), ragged ctx/q lengths, causal self-chunk masking,
bucket-padding rows, sliding windows, and decode-kernel consistency (a chunk
of one token == the paged decode kernel)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.quant.pack import pack_int4

RNG = np.random.default_rng(7)


def _case(b, n_layers, n_pages, ps, w, c, hkv, groups, d, kv_bits):
    """Random head-major pool + shuffled page tables + one query chunk per row."""
    h = hkv * groups
    q = jnp.asarray(RNG.normal(size=(b, c, h, d)), jnp.float32)
    if kv_bits < 16:
        lim = 8 if kv_bits == 4 else 128
        kp = RNG.integers(-lim, lim, (n_layers, n_pages, hkv, ps, d)).astype(np.int8)
        vp = RNG.integers(-lim, lim, (n_layers, n_pages, hkv, ps, d)).astype(np.int8)
        ks = (RNG.random((n_layers, n_pages, hkv, ps)) * 0.1).astype(np.float32)
        vs = (RNG.random((n_layers, n_pages, hkv, ps)) * 0.1).astype(np.float32)
        ck = RNG.integers(-lim, lim, (b, c, hkv, d)).astype(np.int8)
        cv = RNG.integers(-lim, lim, (b, c, hkv, d)).astype(np.int8)
        cks = (RNG.random((b, c, hkv)) * 0.1).astype(np.float32)
        cvs = (RNG.random((b, c, hkv)) * 0.1).astype(np.float32)
    else:
        kp = RNG.normal(size=(n_layers, n_pages, hkv, ps, d)).astype(np.float32)
        vp = RNG.normal(size=(n_layers, n_pages, hkv, ps, d)).astype(np.float32)
        ks = vs = cks = cvs = None
        ck = RNG.normal(size=(b, c, hkv, d)).astype(np.float32)
        cv = RNG.normal(size=(b, c, hkv, d)).astype(np.float32)
    tables = RNG.permutation(n_pages)[: b * w].reshape(b, w).astype(np.int32)
    J = lambda x: None if x is None else jnp.asarray(x)
    return (
        q, J(kp), J(vp), J(ks), J(vs), jnp.asarray(tables),
        J(ck), J(cv), J(cks), J(cvs),
    )


def _packed(x, kv_bits):
    if x is None or kv_bits != 4:
        return x
    return pack_int4(x, axis=-1)


def _prefill(case, ctx, qlen, layer, kv_bits, backend, window=None, interpret=None):
    q, kp, vp, ks, vs, tables, ck, cv, cks, cvs = case
    return ops.paged_mqa_prefill(
        q, _packed(kp, kv_bits), _packed(vp, kv_bits), ks, vs, tables,
        ctx, qlen, layer, _packed(ck, kv_bits), _packed(cv, kv_bits), cks, cvs,
        kv_bits=kv_bits, window=window, backend=backend, interpret=interpret,
    )


def _oracle(case, ctx, qlen, layer, d, window=None):
    q, kp, vp, ks, vs, tables, ck, cv, cks, cvs = case
    return ref.paged_mqa_prefill_ref(
        q, kp, vp, ks, vs, tables, ctx, qlen, layer, ck, cv, cks, cvs,
        sm_scale=1.0 / np.sqrt(d), window=window,
    )


def _rows(got, exp, qlen):
    """Compare only valid chunk rows (padding rows are unspecified)."""
    for row in range(got.shape[0]):
        n = int(qlen[row])
        np.testing.assert_allclose(
            np.asarray(got)[row, :n], np.asarray(exp)[row, :n],
            atol=3e-3, rtol=3e-3,
        )


@pytest.mark.parametrize("kv_bits", [8, 4, 16])
@pytest.mark.parametrize(
    "b,hkv,groups,d,ps,w,c",
    [
        (2, 2, 4, 64, 8, 4, 8),
        (3, 1, 8, 32, 16, 3, 5),  # MQA, non-pow2 batch/width/chunk
        (2, 4, 1, 64, 4, 5, 4),  # MHA
    ],
)
def test_prefill_matches_oracle(kv_bits, b, hkv, groups, d, ps, w, c):
    n_layers, n_pages = 2, b * w
    case = _case(b, n_layers, n_pages, ps, w, c, hkv, groups, d, kv_bits)
    # ragged ctx: full table, page-boundary, cold (0 cached tokens)
    ctx = jnp.asarray([w * ps, ps, 0][:b], jnp.int32)
    qlen = jnp.asarray([c, c - 1, c][:b], jnp.int32)
    for layer in range(n_layers):
        exp = _oracle(case, ctx, qlen, layer, d)
        for backend, interp in (("xla", None), ("pallas", True)):
            got = _prefill(case, ctx, qlen, layer, kv_bits, backend, interpret=interp)
            _rows(got, exp, qlen)


def test_cold_chunk_is_pure_causal_self_attention():
    """ctx == 0 everywhere: the kernel must equal plain causal attention over
    the chunk and read nothing from the (poisoned) pool."""
    b, hkv, groups, d, ps, w, c = 2, 2, 2, 32, 8, 4, 6
    case = _case(b, 1, b * w, ps, w, c, hkv, groups, d, 8)
    q, kp, vp, ks, vs, tables, ck, cv, cks, cvs = case
    case = (q, kp.at[:].set(127), vp.at[:].set(127), ks, vs, tables, ck, cv, cks, cvs)
    ctx = jnp.zeros((b,), jnp.int32)
    qlen = jnp.full((b,), c, jnp.int32)
    exp = _oracle(case, ctx, qlen, 0, d)
    # and against flash attention on the dequantized chunk
    from repro.models.attention import flash_attention

    flash = flash_attention(q, ck * cks[..., None], cv * cvs[..., None], causal=True)
    np.testing.assert_allclose(np.asarray(exp), np.asarray(flash), atol=3e-3, rtol=3e-3)
    for backend, interp in (("xla", None), ("pallas", True)):
        got = _prefill(case, ctx, qlen, 0, 8, backend, interpret=interp)
        _rows(got, exp, qlen)


def test_window_masking_matches_oracle():
    b, hkv, groups, d, ps, w, c = 2, 2, 2, 32, 8, 4, 8
    case = _case(b, 1, b * w, ps, w, c, hkv, groups, d, 8)
    ctx = jnp.asarray([w * ps - 3, 2 * ps], jnp.int32)
    qlen = jnp.asarray([c, c - 2], jnp.int32)
    for window in (3, ps, 2 * ps + 5):
        exp = _oracle(case, ctx, qlen, 0, d, window=window)
        for backend, interp in (("xla", None), ("pallas", True)):
            got = _prefill(
                case, ctx, qlen, 0, 8, backend, window=window, interpret=interp
            )
            _rows(got, exp, qlen)


def test_single_token_chunk_matches_decode_kernel():
    """A chunk of one token must reproduce the paged *decode* kernel (whose
    fused new-token term is the c == 1 special case of the self-chunk)."""
    b, hkv, groups, d, ps, w = 2, 2, 3, 32, 8, 3
    case = _case(b, 1, b * w, ps, w, 1, hkv, groups, d, 8)
    q, kp, vp, ks, vs, tables, ck, cv, cks, cvs = case
    ctx = jnp.asarray([2 * ps + 3, 5], jnp.int32)
    qlen = jnp.ones((b,), jnp.int32)
    got = _prefill(case, ctx, qlen, 0, 8, "xla")
    dec = ops.paged_mqa_decode(
        q[:, 0], kp, vp, ks, vs, tables, ctx, 0,
        ck[:, 0], cv[:, 0], cks[:, 0], cvs[:, 0], kv_bits=8, backend="xla",
    )
    np.testing.assert_allclose(
        np.asarray(got[:, 0]), np.asarray(dec), atol=3e-3, rtol=3e-3
    )


def test_stale_pool_entries_beyond_ctx_are_dead():
    """Corrupting pool positions at/past each row's ctx must not change any
    valid output (clamped slots may be fetched, never used)."""
    b, hkv, groups, d, ps, w, c = 2, 2, 2, 32, 8, 4, 4
    case = _case(b, 1, b * w, ps, w, c, hkv, groups, d, 8)
    q, kp, vp, ks, vs, tables, ck, cv, cks, cvs = case
    ctx = jnp.asarray([ps + 2, 0], jnp.int32)
    qlen = jnp.full((b,), c, jnp.int32)
    kp2 = np.asarray(kp).copy()
    for row in range(b):
        for pos in range(int(ctx[row]), w * ps):
            kp2[0, int(tables[row, pos // ps]), :, pos % ps] = 127
    case2 = (q, jnp.asarray(kp2), vp, ks, vs, tables, ck, cv, cks, cvs)
    for backend, interp in (("xla", None), ("pallas", True)):
        got = _prefill(case, ctx, qlen, 0, 8, backend, interpret=interp)
        got2 = _prefill(case2, ctx, qlen, 0, 8, backend, interpret=interp)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(got2), atol=1e-6, err_msg=backend
        )
