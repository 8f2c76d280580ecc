"""Sharding rules: pattern matching, divisibility validation, tree coverage."""
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import param_spec


def test_param_spec_rules():
    # 2-D weight sharding: TP over "model" + FSDP over "data"
    assert param_spec("embed", 2) == P("model", "data")
    assert param_spec("unembed", 2) == P("data", "model")
    assert param_spec("blocks/wq", 3) == P(None, "data", "model")  # stacked
    assert param_spec("blocks/wo", 3) == P(None, "model", "data")
    assert param_spec("blocks/moe/wg", 4) == P(None, "model", "data", None)
    assert param_spec("blocks/mlp/wd", 3) == P(None, "model", "data")
    assert param_spec("blocks/norm1", 2) == P(None, None)
    assert param_spec("final_norm", 1) == P(None)


def test_param_spec_fallback_candidates(subproc):
    """mixtral-style: 8 experts < 16 model shards -> the fallback candidate
    shards the matrix dims instead of replicating 140 GB of experts."""
    subproc(
        """
        from repro.launch.mesh import make_mesh
        import jax
        from jax.sharding import PartitionSpec as P
        from repro.distributed.sharding import param_spec

        mesh = make_mesh((2, 2), ("data", "model"))
        # E=8 divisible -> experts sharded
        got = param_spec("blocks/moe/wg", 4, (56, 8, 6144, 16384), mesh)
        assert got == P(None, "model", "data", None), got
        # E=3 NOT divisible -> fallback shards D(data) x F(model)
        got2 = param_spec("blocks/moe/wg", 4, (56, 3, 6144, 16384), mesh)
        assert got2 == P(None, None, "data", "model"), got2
        print("fallback OK")
        """,
        n_devices=4,
    )


def test_validate_spec_drops_nondivisible(subproc):
    subproc(
        """
        from repro.launch.mesh import make_mesh
        import jax
        from jax.sharding import PartitionSpec as P
        from repro.distributed.sharding import validate_spec

        mesh = make_mesh((2, 2), ("data", "model"))
        # 7 not divisible by 2 -> dropped; 8 divisible -> kept
        got = validate_spec(P("data", "model"), (7, 8), mesh)
        assert got == P(None, "model"), got
        got2 = validate_spec(P(("data", "model"), None), (8, 3), mesh)
        assert got2 == P(("data", "model"), None), got2
        got3 = validate_spec(P(("data", "model"), None), (6, 3), mesh)
        assert got3 == P(None, None), got3
        print("validate OK")
        """,
        n_devices=4,
    )


def test_tree_shardings_cover_reduced_arch(subproc):
    """Every parameter of every family gets a consistent sharding on a real
    mesh, and the big 2-D weights are actually model-sharded."""
    subproc(
        """
        from repro.launch.mesh import make_mesh
        import jax
        from functools import partial
        from repro.configs import get_config
        from repro.distributed.sharding import tree_shardings
        from repro.models import transformer as T

        mesh = make_mesh((2, 2), ("data", "model"))
        for name in ("yi-9b", "kimi-k2-1t-a32b", "zamba2-7b", "mamba2-130m"):
            cfg = get_config(name).reduced()
            shapes = jax.eval_shape(partial(T.init_params, cfg), jax.random.PRNGKey(0))
            sh = tree_shardings(shapes, mesh)
            flat = jax.tree_util.tree_leaves(sh)
            assert len(flat) == len(jax.tree_util.tree_leaves(shapes))
            # embed is vocab-sharded (padded vocab divisible by 256)
            assert "model" in str(sh["embed"].spec)
        print("tree shardings OK")
        """,
        n_devices=4,
    )
