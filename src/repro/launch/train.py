"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b \
        --steps 300 --batch 8 --seq 512 [--reduced] [--mesh-data N --mesh-model M]

On a real TPU pod this binary runs per host (jax.distributed.initialize);
here it drives the same Trainer on whatever devices exist.
"""
from __future__ import annotations

import argparse
import dataclasses
import json


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--reduced", action="store_true", help="CPU-sized config")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh-data", type=int, default=0, help="0 = no mesh (single device)")
    ap.add_argument("--mesh-model", type=int, default=1)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    from repro.configs import get_config
    from repro.data.pipeline import DataConfig
    from repro.distributed import sharding as sh
    from repro.distributed.fault import run_with_restarts
    from repro.launch.mesh import make_mesh
    from repro.train import TrainConfig, Trainer

    arch = get_config(args.arch)
    if args.reduced:
        arch = arch.reduced()
    arch = dataclasses.replace(arch, remat="none" if args.reduced else arch.remat)

    mesh = None
    if args.mesh_data:
        mesh = make_mesh((args.mesh_data, args.mesh_model), ("data", "model"))
        sh.set_mesh(mesh)

    tc = TrainConfig(
        lr=args.lr,
        warmup=max(args.steps // 10, 1),
        total_steps=args.steps,
        microbatches=args.microbatches,
        ckpt_every=args.ckpt_every,
        ckpt_dir=args.ckpt_dir,
    )
    data = DataConfig(vocab=arch.vocab, seq_len=args.seq, global_batch=args.batch)
    trainer = Trainer(arch=arch, tc=tc, data=data, mesh=mesh)

    def attempt(start_step: int) -> dict:
        return trainer.run(args.steps, start_step=start_step)

    out = run_with_restarts(
        attempt,
        max_restarts=3,
        on_restart=lambda n, e: print(f"[train] restart {n} after {e!r}"),
    )
    hist = out["history"]
    print(json.dumps({
        "arch": arch.name,
        "steps": len(hist),
        "first_loss": hist[0]["loss"],
        "final_loss": hist[-1]["loss"],
        "mean_step_s": sum(h["sec"] for h in hist) / max(len(hist), 1),
        "stragglers": trainer.monitor.stragglers,
    }, indent=1))


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
