"""Pallas mpmm kernel vs the pure-jnp oracle: shape/dtype/precision/dataflow
sweep in interpret mode (kernel body executes on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.quant.pack import pack_int4

RNG = np.random.default_rng(0)


def _float_case(m, k, n):
    x = jnp.asarray(RNG.normal(size=(m, k)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(k, n)), jnp.float32)
    return x, w


@pytest.mark.parametrize("w_bits", [4, 8])
@pytest.mark.parametrize("dataflow", ["cf", "ff"])
@pytest.mark.parametrize(
    "m,k,n",
    [(8, 128, 128), (96, 384, 160), (1, 256, 512), (130, 520, 130)],
)
def test_dequant_sweep(w_bits, dataflow, m, k, n):
    x, w = _float_case(m, k, n)
    wd, ws = ops.pack_weights(w, w_bits)
    got = ops.mpmm(x, wd, ws, w_bits=w_bits, mode="dequant", dataflow=dataflow)
    exp = ref.mpmm_ref(x, wd, ws, w_bits=w_bits, mode="dequant")
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("w_bits,x_bits", [(16, 16), (8, 8), (4, 8), (8, 16), (16, 8)])
@pytest.mark.parametrize("dataflow", ["cf", "ff"])
def test_int_mode_bit_exact(w_bits, x_bits, dataflow):
    m, k, n = 32, 256, 128
    xlim = 2 ** (x_bits - 1) - 1
    x = jnp.asarray(
        RNG.integers(-xlim, xlim, (m, k)), jnp.int16 if x_bits == 16 else jnp.int8
    )
    wlim = 7 if w_bits == 4 else 2 ** (w_bits - 1) - 1
    wq = RNG.integers(-wlim - 1, wlim + 1, (k, n))
    wq = jnp.asarray(wq, jnp.int16 if w_bits == 16 else jnp.int8)
    wd = pack_int4(wq.astype(jnp.int8), axis=0) if w_bits == 4 else wq
    ws = jnp.ones((1, n), jnp.float32)
    got_scaled = ops.mpmm(x, wd, ws, w_bits=w_bits, x_bits=x_bits, mode="int", dataflow=dataflow)
    exp = ref.mpmm_ref(x, wd, ws, w_bits=w_bits, mode="int")
    np.testing.assert_array_equal(
        np.asarray(got_scaled), np.asarray(exp).astype(np.float32)
    )


def test_int_wraparound_semantics():
    """int32 accumulator wraparound matches the 32-bit SAU semantics."""
    m, k, n = 8, 256, 128
    x = jnp.full((m, k), 32767, jnp.int16)
    wq = jnp.full((k, n), 32767, jnp.int16)
    ws = jnp.ones((1, n), jnp.float32)
    got = ops.mpmm(x, wq, ws, w_bits=16, x_bits=16, mode="int")
    exp = ref.mpmm_ref(x, wq, ws, w_bits=16, mode="int").astype(np.float32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))


def test_batched_leading_dims():
    x = jnp.asarray(RNG.normal(size=(2, 3, 256)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(256, 128)), jnp.float32)
    wd, ws = ops.pack_weights(w, 8)
    got = ops.mpmm(x, wd, ws, w_bits=8)
    assert got.shape == (2, 3, 128)
    exp = ref.mpmm_ref(x.reshape(-1, 256), wd, ws, w_bits=8, mode="dequant").reshape(2, 3, 128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), atol=2e-3)


def test_w16_dequant_rejected():
    x, w = _float_case(8, 128, 128)
    wd, ws = jnp.zeros((128, 128), jnp.int16), jnp.ones((1, 128), jnp.float32)
    with pytest.raises(ValueError):
        ops.mpmm(x, wd, ws, w_bits=16, mode="dequant")


def test_auto_dataflow_dispatch():
    x, w = _float_case(64, 256, 128)
    wd, ws = ops.pack_weights(w, 8)
    got = ops.mpmm(x, wd, ws, w_bits=8, dataflow="auto")
    exp = ref.mpmm_ref(x, wd, ws, w_bits=8, mode="dequant")
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), atol=2e-3)


@pytest.mark.parametrize("w_bits", [4, 8])
def test_ff_accumulates_partials_in_f32_at_large_k(w_bits):
    """Regression: the FF kernel used to accumulate cross-K-stage partial
    sums in the bf16 *output* dtype (and the wrapper applied w_scale in
    bf16), diverging from CF's f32 VMEM accumulator as K grows.  Both
    dataflows now run the same f32 stage-sum in the same order, so at
    K = 4096 (8 stages) they must agree bit-for-bit and sit within one bf16
    rounding of the f32 oracle."""
    m, k, n = 8, 4096, 128
    x = jnp.asarray(RNG.normal(size=(m, k)), jnp.bfloat16)
    w = jnp.asarray(RNG.normal(size=(k, n)), jnp.float32)
    wd, ws = ops.pack_weights(w, w_bits)
    ff = ops.mpmm(x, wd, ws, w_bits=w_bits, mode="dequant", dataflow="ff")
    cf = ops.mpmm(x, wd, ws, w_bits=w_bits, mode="dequant", dataflow="cf")
    assert ff.dtype == cf.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(ff, np.float32), np.asarray(cf, np.float32)
    )
    exp = ref.mpmm_ref(x, wd, ws, w_bits=w_bits, mode="dequant")
    np.testing.assert_allclose(
        np.asarray(ff, np.float32), np.asarray(exp, np.float32),
        rtol=1e-2, atol=1e-2,
    )


def test_xla_backend_matches_pallas():
    x, w = _float_case(32, 256, 128)
    wd, ws = ops.pack_weights(w, 4)
    a = ops.mpmm(x, wd, ws, w_bits=4, backend="pallas")
    b = ops.mpmm(x, wd, ws, w_bits=4, backend="xla")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3)


def _stack(k, n, w_bits, layers=3):
    w = jnp.asarray(RNG.normal(size=(layers, k, n)), jnp.float32)
    return ops.pack_weights(w, w_bits)  # [L, K (/2), N], [L, 1, N]


@pytest.mark.parametrize("w_bits", [4, 8])
@pytest.mark.parametrize("m", [1, 5, 16, 96])
@pytest.mark.parametrize(
    "k,n",
    [(768, 384), (256, 128)],  # K a multiple of 256 not 512, N of 128 not 512
)
def test_stacked_weight_read_at_a_traced_layer(w_bits, m, k, n):
    """The kernel reads layer ``li`` of a stacked [L, K/kpack, N] payload in
    place, the index traced (as inside the serving path's layer scan)."""
    x = jnp.asarray(RNG.normal(size=(m, k)), jnp.bfloat16)
    wd, ws = _stack(k, n, w_bits)
    run = jax.jit(lambda x, li: ops.mpmm(x, wd, ws, li, w_bits=w_bits))
    for li in (0, 2):
        got = run(x, jnp.int32(li))
        exp = ref.mpmm_ref(x, wd[li], ws[li], w_bits=w_bits, mode="dequant")
        assert got.shape == (m, n) and got.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(exp, np.float32),
            rtol=1e-2, atol=1e-2,
        )


def test_stacked_weight_needs_its_layer():
    wd, ws = _stack(256, 128, 8)
    with pytest.raises(ValueError):
        ops.mpmm(jnp.ones((2, 256), jnp.bfloat16), wd, ws, w_bits=8)


@pytest.mark.parametrize(
    "m,n,k,kpack",
    [(16, 11008, 4096, 2), (16, 4096, 11008, 2), (16, 64000, 4096, 1),
     (512, 4096, 11008, 2), (96, 384, 768, 2), (130, 130, 520, 1)],
)
def test_blocks_divide_the_weight(m, n, k, kpack):
    """Blocks divide the weight's dims (or equal them), so a weight is never
    padded; only the activation's rows are, to a multiple of ``bm``."""
    bm, bn, bk = ops._pick_blocks(m, n, k, kpack)
    assert n % bn == 0 and k % bk == 0 and bm <= max(m, 8)
    assert bn % 128 == 0 or bn == n
    assert (bk // kpack) % 128 == 0 or bk == k


def test_dense_routes_quantized_weights_by_platform_and_mesh(monkeypatch):
    """On a TPU with no mesh active, quantized projections take the Pallas
    kernel; on the CPU, or under a mesh, the XLA reference."""
    from repro.distributed import sharding
    from repro.models import layers

    x = jnp.asarray(RNG.normal(size=(3, 384)), jnp.bfloat16)
    w = layers.quantize_dense_weight(
        jnp.asarray(RNG.normal(size=(384, 256)), jnp.float32), 4
    )
    # a fresh function each time: make_jaxpr caches traces by function
    uses_kernel = lambda: "pallas_call" in str(
        jax.make_jaxpr(lambda x, w: layers.dense(x, w))(x, w)
    )
    assert layers.mpmm_backend() == "xla" and not uses_kernel()
    exp = layers.dense(x, w)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ops, "_interpret_default", lambda: True)
    assert layers.mpmm_backend() == "pallas" and uses_kernel()
    np.testing.assert_allclose(
        np.asarray(layers.dense(x, w), np.float32), np.asarray(exp, np.float32),
        rtol=1e-2, atol=1e-2,
    )
    sharding.set_mesh(jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",)))
    try:
        assert layers.mpmm_backend() == "xla" and not uses_kernel()
    finally:
        sharding.set_mesh(None)


def test_layer_scan_hands_dense_the_whole_stack():
    """split_stacked keeps quantized [L, K, N] projections out of the scan's
    per-layer slices; layer_view puts them back whole with the index."""
    from repro.models import layers

    q = layers.quantize_dense_weight(jnp.ones((2, 256, 128), jnp.float32), 4)
    experts = layers.quantize_dense_weight(jnp.ones((2, 3, 256, 128), jnp.float32), 8)
    blocks = {"norm1": jnp.ones((2, 256)), "wq": q,
              "mlp": {"wg": q, "wd": jnp.ones((2, 128, 256))}, "moe": {"wg": experts}}
    xs, stacked = layers.split_stacked(blocks)
    assert set(xs) == {"norm1", "mlp", "moe"} and set(xs["mlp"]) == {"wd"}
    assert xs["moe"]["wg"] is experts  # expert stacks are sliced as before
    p = jax.tree_util.tree_map(lambda a: a[1], xs)
    view = layers.layer_view(p, stacked, jnp.int32(1))
    assert view["wq"]["data"] is q["data"] and int(view["wq"]["layer"]) == 1
    assert view["mlp"]["wg"]["data"].shape == (2, 128, 128)
    assert view["mlp"]["wd"].shape == (128, 256) and view["norm1"].shape == (256,)
