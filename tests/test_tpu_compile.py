"""Ahead-of-time compiles of the served path's Pallas kernels for a described
TPU v5e (no chip needed): paged decode and paged prefill/verify at kv4/kv8/
kv16 and ``mpmm`` at W4/W8, at llama3.2-3b widths, and ``mpmm`` at every
yi-9b projection shape read from a stack of layers.  Interpret mode cannot
see what the TPU compiler refuses (block shapes off the (8, 128) tiling,
int8 shifts, VMEM limits); these compiles can.

The topology is described inside a module fixture: only one process at a
time may load the TPU compiler's library, so describing it at import would
break test collection under several workers.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops

CFG = get_config("llama3.2-3b")
B, W, N_PAGES, PS = 4, 16, 64, 16  # rows, table width, pool pages, page size


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU compile written to a persistent cache cannot be read back here
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _pool_args(sharding, kv_bits):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    hkv, d = CFG.n_kv_heads, CFG.hd
    dk = d // 2 if kv_bits == 4 else d
    dt = jnp.int8 if kv_bits < 16 else jnp.bfloat16
    pool = s((CFG.n_layers, N_PAGES, hkv, PS, dk), dt)
    scale = s((CFG.n_layers, N_PAGES, hkv, PS), jnp.float32) if kv_bits < 16 else None
    tok = lambda *lead: (
        s((*lead, hkv, dk), dt),
        s((*lead, hkv), jnp.float32) if kv_bits < 16 else None,
    )
    return s, pool, scale, tok


@pytest.mark.parametrize("kv_bits", [4, 8, 16])
def test_paged_decode_compiles(one_chip, kv_bits):
    s, pool, scale, tok = _pool_args(one_chip, kv_bits)
    new, new_scale = tok(B)
    text = _compiled_text(
        lambda *a: ops.paged_mqa_decode(
            *a, kv_bits=kv_bits, backend="pallas", interpret=False
        ),
        s((B, CFG.n_heads, CFG.hd), jnp.bfloat16), pool, pool, scale, scale,
        s((B, W), jnp.int32), s((B,), jnp.int32), s((), jnp.int32),
        new, new, new_scale, new_scale,
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("chunk", [32, 5])  # a prefill chunk; a spec_k=4 verify window
@pytest.mark.parametrize("kv_bits", [4, 8, 16])
def test_paged_prefill_compiles(one_chip, kv_bits, chunk):
    s, pool, scale, tok = _pool_args(one_chip, kv_bits)
    new, new_scale = tok(B, chunk)
    text = _compiled_text(
        lambda *a: ops.paged_mqa_prefill(
            *a, kv_bits=kv_bits, backend="pallas", interpret=False
        ),
        s((B, chunk, CFG.n_heads, CFG.hd), jnp.bfloat16), pool, pool, scale, scale,
        s((B, W), jnp.int32), s((B,), jnp.int32), s((B,), jnp.int32),
        s((), jnp.int32), new, new, new_scale, new_scale,
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dataflow", ["cf", "ff"])
@pytest.mark.parametrize("w_bits", [4, 8])
def test_mpmm_compiles(one_chip, w_bits, dataflow):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    m, k, n = 128, CFG.d_model, CFG.d_ff
    text = _compiled_text(
        lambda x, w, sc: ops.mpmm(
            x, w, sc, w_bits=w_bits, dataflow=dataflow, interpret=False
        ),
        s((m, k), jnp.bfloat16),
        s((k // 2 if w_bits == 4 else k, n), jnp.int8),
        s((1, n), jnp.float32),
    )
    assert "tpu_custom_call" in text


YI = get_config("yi-9b")
_MOVES = re.compile(r"= \w+\[([\d,]*)\]\S* (copy|pad|dynamic-slice)\(")


@pytest.mark.parametrize("w_bits", [4, 8])
@pytest.mark.parametrize(
    "k,n",
    [(YI.d_model, YI.n_heads * YI.hd), (YI.d_model, YI.n_kv_heads * YI.hd),
     (YI.d_model, YI.d_ff), (YI.d_ff, YI.d_model), (YI.d_model, YI.vocab)],
)
def test_mpmm_reads_a_stacked_yi_9b_weight_in_place(one_chip, w_bits, k, n):
    """Decode-sized rows against one layer of a 16-layer stack, the layer a
    traced index: the kernel is in the program, and nothing the size of a
    layer's weight is copied, padded or sliced on the way to it."""
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    kp = k // 2 if w_bits == 4 else k
    text = _compiled_text(
        lambda x, w, sc, li: ops.mpmm(x, w, sc, li, w_bits=w_bits, interpret=False),
        s((16, k), jnp.bfloat16), s((16, kp, n), jnp.int8),
        s((16, 1, n), jnp.float32), s((), jnp.int32),
    )
    assert "tpu_custom_call" in text
    for dims, op in _MOVES.findall(text):
        size = 1
        for d in filter(None, dims.split(",")):
            size *= int(d)
        assert size < kp * n, (op, dims)
