"""Find an open-loop cell's knee: the highest arrival rate it sustains
without a growing queue.  One process, one set-up, one window per rate:

    python3 bench/knee.py --workload <cell> --seed <n> --seconds <s> --rates 1,1.5,2

For each rate it prints the waiting queue's growth over the window (least
squares slope of the requests due and not yet admitted, times the window),
the queue at the window's end, the median and 90th percentile of time to
first token and completed over offered requests.  A rate is sustained when the queue
grows by fewer than two requests over the window.  The benchmark's runs use
a fixed rate from the traffic file; this sweep is how that rate was found.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="open-loop knee sweep")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from bench import harness, warmup
    from bench.measure import RunData
    from bench.weights import make_weights

    harness.enable_compile_cache()
    p = harness.plan(args.workload)
    harness.device_info(True, p["chips"])
    weights = make_weights(p["model"], args.seed)
    jax.block_until_ready(weights)
    engine = harness._engine(p, weights)
    del weights
    rng = np.random.default_rng([args.seed, 3])
    warmup.sweep(engine, p["mix"], p["model"]["vocab_size"], rng)
    print(f"set-up {time.perf_counter() - T_START:.1f} s", file=sys.stderr, flush=True)
    ttft = harness.reader("ttft_p50_ms.open_loop")
    for rate in (float(r) for r in args.rates.split(",")):
        client = harness._client(p, engine, args.seed, rate)
        harness._roll_forward(client, p["mix"])
        client.record = True
        t0 = time.perf_counter()
        client.run_until(t0 + args.seconds)
        t1 = time.perf_counter()
        run = RunData(model=p["model"], mix=p["mix"], peaks={}, closed=False, t0=t0,
                      t1=t1, setup_s=0.0, flights=client.flights, steps=client.steps,
                      stats0={}, stats1={})
        t = np.array([s.t1 - t0 for s in client.steps])
        q = np.array([s.waiting for s in client.steps], float)
        slope = float(np.polyfit(t, q, 1)[0]) if len(t) > 2 else 0.0
        due = run.due_in_window()
        done = sum(1 for f in due if f.done)
        row = {
            "rate_per_s": rate, "due": len(due), "completed": done,
            "queue_growth": slope * (t1 - t0), "queue_end": int(q[-1]) if len(q) else 0,
            "ttft_p50_ms": ttft(run), "ttft_p90_ms": run.notes.get("ttft_p90_ms"),
            "steps": len(client.steps),
            "sustained": slope * (t1 - t0) < 2.0,
        }
        print(json.dumps(row), flush=True)
        client.drain(time.perf_counter() + 600)
    return 0


if __name__ == "__main__":
    sys.exit(main())
