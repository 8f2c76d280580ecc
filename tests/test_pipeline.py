"""Pipeline parallelism over the pod axis: exactness vs sequential stages."""


def test_pipeline_matches_sequential(subproc):
    subproc(
        """
        from repro.launch.mesh import make_mesh
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.pipeline import pipeline_apply

        mesh = make_mesh((4,), ("pod",))
        n_stages, n_micro, bm, d = 4, 8, 2, 16
        rng = np.random.default_rng(0)
        w = jnp.asarray(rng.normal(size=(n_stages, d, d)) * 0.3, jnp.float32)
        x = jnp.asarray(rng.normal(size=(n_micro, bm, d)), jnp.float32)

        def stage_fn(p, mb):
            return jnp.tanh(mb @ p)

        got = pipeline_apply(stage_fn, w, x, mesh, axis="pod")

        ref = x
        for s in range(n_stages):
            ref = jax.vmap(lambda mb: stage_fn(w[s], mb))(ref)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5, rtol=1e-5)
        print("pipeline OK")
        """,
        n_devices=4,
    )


def test_pipeline_single_stage_degenerate(subproc):
    subproc(
        """
        from repro.launch.mesh import make_mesh
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.pipeline import pipeline_apply

        mesh = make_mesh((1,), ("pod",))
        w = jnp.ones((1, 4, 4), jnp.float32)
        x = jnp.ones((3, 2, 4), jnp.float32)
        got = pipeline_apply(lambda p, mb: mb @ p, w, x, mesh, axis="pod")
        np.testing.assert_allclose(np.asarray(got), np.asarray(x @ w[0]), atol=1e-6)
        print("degenerate pipeline OK")
        """,
        n_devices=1,
    )
