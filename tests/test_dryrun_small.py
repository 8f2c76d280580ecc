"""The dry-run machinery end-to-end on a small mesh (subprocess, 16 devices):
lower + compile + memory/cost/collective extraction for reduced configs.

The full 512-device production sweep runs via `python -m repro.launch.dryrun
--all` (results recorded in EXPERIMENTS.md); this test keeps the machinery
honest in CI time."""
import pytest


@pytest.mark.parametrize(
    "arch,kind",
    [("llama3.2-3b", "train"), ("yi-9b", "decode"), ("mamba2-130m", "decode"),
     ("kimi-k2-1t-a32b", "train")],
)
def test_small_mesh_cell(subproc, arch, kind):
    subproc(
        f"""
        from repro.launch.mesh import make_mesh
        import dataclasses, jax, jax.numpy as jnp
        from functools import partial
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.configs.shapes import ShapeSpec
        from repro.distributed import sharding as sh
        from repro.launch import dryrun as dr

        mesh = make_mesh((4, 4), ("data", "model"))
        arch = dataclasses.replace(
            get_config("{arch}").reduced(), remat="none",
        )
        shape = ShapeSpec("t", seq_len=128, global_batch=8, kind="{kind}")
        lowered = dr.build_lowered(arch, shape, mesh)
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        if isinstance(cost, list):
            cost = cost[0]
        assert cost.get("flops", 0) > 0
        hlo = compiled.as_text()
        coll = dr.collective_bytes_from_hlo(hlo)
        total = sum(coll.values())
        print("collectives:", coll)
        assert total > 0, "sharded model must communicate"
        print("small dryrun OK", "{arch}", "{kind}")
        """,
        n_devices=16,
        timeout=900,
    )


def test_collective_parser_units():
    from repro.launch.dryrun import collective_bytes_from_hlo

    hlo = """
  %x = f32[128,256]{1,0} all-reduce(f32[128,256]{1,0} %p), replica_groups={}
  %y = bf16[64]{0} all-gather(bf16[32]{0} %q), dimensions={0}
  %z = f32[8,8]{1,0} add(f32[8,8]{1,0} %a, f32[8,8]{1,0} %b)
  %w = (s8[1024]{0}, s8[1024]{0}) all-to-all(s8[1024]{0} %c, s8[1024]{0} %d)
"""
    out = collective_bytes_from_hlo(hlo)
    # output-operand bytes per op (operands inside parens are not re-counted)
    assert out["all-reduce"] == 128 * 256 * 4
    assert out["all-gather"] == 64 * 2
    assert out["all-to-all"] == 2 * 1024
    assert out["reduce-scatter"] == 0
