"""Run one cell of the benchmark and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  Exits non-zero, printing no result, when
JAX finds no TPU or fewer chips than the cell asks for.  ``--control 1``
serves every request one weight precision lower than its traffic states
(bf16 -> W8, W8 -> W4): the lower-precision control that the check's limit
must fail; the benchmark's own runs never set it.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import repro.serve  # noqa: F401  (the system under test: absent, no run)
    from bench import harness

    harness.enable_compile_cache()
    try:
        p = harness.plan(args.workload)
        result = harness.run(
            p, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
            t_start=T_START, control=bool(args.control),
        )
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    except Exception:
        traceback.print_exc()
        return 1
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
