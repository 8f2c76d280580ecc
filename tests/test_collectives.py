"""int8 compressed gradient reduction: fidelity, error feedback, wire bytes."""
import jax.numpy as jnp
import numpy as np

from repro.distributed.collectives import _CHUNK, _dequantize_chunks, _quantize_chunks


def test_chunk_quantize_roundtrip_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4096,)) * 10, jnp.float32)
    q, s = _quantize_chunks(x, n_shards=4)
    assert q.dtype == jnp.int8
    back = _dequantize_chunks(q, s, 4096)
    # error bounded by scale/2 per chunk
    err = np.abs(np.asarray(back - x))
    bound = np.repeat(np.asarray(s).reshape(-1), _CHUNK)[:4096] / 2 + 1e-6
    assert (err <= bound).all()


def test_wire_bytes_are_quarter_fp32():
    x = jnp.zeros((1 << 16,), jnp.float32)
    q, s = _quantize_chunks(x, n_shards=8)
    wire = q.size * 1 + s.size * 4
    assert wire < 0.3 * x.size * 4


def test_compressed_psum_mean_multidevice(subproc):
    subproc(
        """
        from repro.launch.mesh import make_mesh
        import jax, jax.numpy as jnp, numpy as np
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.distributed.collectives import compressed_psum_mean

        mesh = make_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        per_shard = jnp.asarray(rng.normal(size=(8, 4096)) * 5, jnp.float32)

        def local(x):
            g = x[0]  # my shard's gradient
            red = compressed_psum_mean(g, "data")
            exact = jax.lax.pmean(g, "data")
            return red[None], exact[None]

        red, exact = shard_map(
            local, mesh=mesh, in_specs=P("data", None),
            out_specs=(P("data", None), P("data", None)), check_rep=False,
        )(per_shard)
        red, exact = np.asarray(red), np.asarray(exact)
        # every shard got the same reduced value
        assert np.allclose(red, red[0], atol=1e-6)
        # compressed mean close to exact mean (two int8 stages)
        scale = np.abs(exact).max()
        assert np.abs(red - exact).max() < 0.05 * scale, np.abs(red-exact).max()
        print("compressed psum OK", np.abs(red - exact).max())
        """,
        n_devices=8,
    )


def test_error_feedback_reduces_bias(subproc):
    """With error feedback, repeated reductions of the SAME gradient converge
    to the exact mean (the residual re-enters each round)."""
    subproc(
        """
        from repro.launch.mesh import make_mesh
        import jax, jax.numpy as jnp, numpy as np
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.distributed.collectives import compressed_psum_mean

        mesh = make_mesh((4,), ("data",))
        rng = np.random.default_rng(1)
        g_all = jnp.asarray(rng.normal(size=(4, 2048)), jnp.float32)

        def local(g_shard):
            g = g_shard[0]
            e = jnp.zeros_like(g)
            e2 = jnp.zeros((1, 1024), jnp.float32)  # 2048/4 shards -> 512 pad 1024
            exact = jax.lax.pmean(g, "data")
            errs = []
            acc = jnp.zeros_like(g)   # what the optimizer accumulated
            acc_exact = jnp.zeros_like(g)
            for _ in range(6):
                red, e, e2 = compressed_psum_mean(g + e, "data", e2)
                acc = acc + red
                acc_exact = acc_exact + exact
                errs.append(jnp.max(jnp.abs(acc - acc_exact)))
            return jnp.stack(errs)[None]

        errs = shard_map(local, mesh=mesh, in_specs=P("data", None),
                         out_specs=P("data", None), check_rep=False)(g_all)
        errs = np.asarray(errs)[0]
        # with two-stage error feedback the cumulative sum telescopes: the
        # error must NOT grow ~linearly with rounds
        assert errs[-1] < 2.0 * errs[0] + 1e-4, errs
        print("error feedback OK", errs)
        """,
        n_devices=4,
    )
