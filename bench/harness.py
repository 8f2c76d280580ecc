"""One run of one cell: set-up, the measured window, the metrics, the check.

Everything that belongs to a cell is found by name: the cell in
``BENCHMARK.json``, its configuration file and the block that file names
(``bench/blocks/<block>.py``), its traffic file
(``bench/traffic/<traffic>.json``), its limits (``bench/cells/<cell>.json``)
and one reader per metric (``bench/metrics/<metric>.py``).
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


# ------------------------------------------------------------------ plan
def plan(workload: str, bench: dict | None = None) -> dict:
    """Resolve a cell of BENCHMARK.json into the files and metrics it uses."""
    from bench import check, modelcfg

    bench = bench or json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    cfg = next(c for c in bench["configs"] if c["name"] == cell["config"])
    e2e = [m for m in bench["end_to_end"] if workload in m.get("workloads", [workload])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [
        m for m in bench["per_layer"]
        if workload in m.get("workloads", [workload] if m["moves"] in e2e_names else [])
    ]
    mix = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    limits = check.load_limits(workload)
    missing = sorted(set(_numbers(mix)) - set(limits["checks"]))
    if missing:
        raise KeyError(f"bench/cells/{workload}.json has no limit for {missing}")
    return {
        "workload": workload,
        "chips": cell["chips"],
        "model": {**modelcfg.load(ROOT / cfg["file"]), "name": cfg["name"]},
        "mix": mix,
        "limits": limits,
        "end_to_end": e2e,
        "per_layer": per_layer,
    }


def reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# --------------------------------------------------------------- pieces
class CompileCounter:
    """Counts programs lowered and compiled while ``on``."""

    def __init__(self):
        import jax

        self.on = False
        self.lowered = 0
        self.compiled = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **_):
        if not self.on:
            return
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.lowered += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            self.compiled += 1


def enable_compile_cache() -> str:
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir


def device_info(require_tpu: bool, chips: int) -> dict:
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {devs[0].platform}")
    if require_tpu and len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _counters(stats) -> dict:
    return {k: v for k, v in vars(stats).items() if isinstance(v, (int, float))}


def _engine(p: dict, weights):
    from bench.modelcfg import arch_config
    from repro.serve.engine import ServeEngine

    e = p["mix"]["engine"]
    return ServeEngine(
        arch_config(p["model"]), weights, max_slots=e["max_slots"],
        num_pages=e["num_pages"], page_size=e["page_size"],
        prefill_chunk=e["prefill_chunk"],
    )


def _client(p: dict, engine, seed: int, rate=None):
    from bench.client import Client
    from bench.traffic import Traffic

    mix = p["mix"]
    if rate is not None:
        mix = {**mix, "arrival": {**mix["arrival"], "rate_per_s": rate}}
    closed = mix["loop"] == "closed"
    source = Traffic(mix, seed, p["model"]["vocab_size"]).requests()
    in_flight = mix["engine"]["max_slots"] + mix.get("backlog", 0)
    return Client(engine, source, closed=closed, in_flight=in_flight)


def _roll_forward(client, mix: dict) -> None:
    warm = mix["roll_forward"]
    client.origin = time.perf_counter()
    if client.closed:
        client.run_until_completed(warm["completed"], client.origin + warm["limit_s"])
    else:
        client.run_until(client.origin + warm["seconds"])


# ------------------------------------------------------------------ run
def run(p: dict, *, seed: int, seconds: float, trace: bool, t_start: float,
        require_tpu: bool = True, control: bool = False,
        steps: int | None = None) -> dict:
    """One run; returns the result line's object (without printing it).
    ``steps``: a closed loop's window of exactly that many engine steps in
    place of ``seconds``, so that a run serves the same on any machine."""
    import jax

    from bench import check, devtrace, warmup
    from bench.measure import RunData
    from bench.peaks import peaks_for
    from bench.traffic import context_range
    from bench.weights import make_weights

    device = device_info(require_tpu, p["chips"])
    peaks = peaks_for(device["kind"]) if require_tpu else p.get("peaks")
    model, mix = p["model"], p["mix"]
    counter = CompileCounter()

    phases = {"imports_s": time.perf_counter() - t_start}
    counter.on = True
    t = time.perf_counter()
    weights = make_weights(model, seed)
    jax.block_until_ready(weights)
    engine = _engine(p, weights)
    del weights
    phases["weights_s"] = time.perf_counter() - t
    # the control: the program's own next-lower path in the program's place
    serve_as = check.control_paths(p["limits"]) if control else {}
    rng = np.random.default_rng([int(seed), 3])
    t = time.perf_counter()
    warm_steps = warmup.sweep(engine, mix, model["vocab_size"], rng, serve_as)
    phases["sweep_s"] = time.perf_counter() - t
    t = time.perf_counter()
    client = _client(p, engine, seed)
    client.serve_as = serve_as
    _roll_forward(client, mix)
    phases["roll_forward_s"] = time.perf_counter() - t
    phases["setup_programs_lowered"] = counter.lowered
    phases["setup_programs_compiled"] = counter.compiled
    counter.lowered = counter.compiled = 0
    stats0 = _counters(engine.stats)
    setup_s = time.perf_counter() - t_start

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    client.record = True
    if trace:
        jax.profiler.start_trace(trace_dir)
    with jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN):
        t0 = time.perf_counter()
        if steps is None:
            client.run_until(t0 + seconds)
        else:
            client.run_steps(steps)
        t1 = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    counter.on = False
    stats1 = _counters(engine.stats)
    mem = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use", 0)
    device["memory_peak_bytes"] = int(mem)

    reduced = None
    if trace:
        reduced = devtrace.reduce_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
    data = RunData(
        model=model, mix=mix, peaks=peaks, closed=client.closed, t0=t0, t1=t1,
        setup_s=setup_s, flights=client.flights, steps=client.steps,
        stats0=stats0, stats1=stats1, trace=reduced,
    )
    specs = p["per_layer"] if trace else p["end_to_end"]
    metrics = {}
    for m in specs:
        value = reader(m["name"])(data)
        if value is not None and math.isfinite(value):
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    window = data.due_in_window()
    failed = sum(1 for f in window if f.failed or (f.req is not None and f.req.failed))
    late = [f.submit_t - f.due_t for f in window if not client.closed]
    finished = [
        {"prompt": f.spec.prompt, "served": list(f.req.out_tokens),
         "w_bits": f.spec.w_bits, "kv_bits": f.spec.kv_bits,
         "temperature": f.spec.temperature, "top_p": f.spec.top_p,
         "seed": f.spec.seed}
        for f in client.flights
        if f.req is not None and f.req.done
        and len(f.req.out_tokens) == f.spec.max_new_tokens
    ]
    info = {
        **phases,
        "warmup_steps": warm_steps,
        "window_steps": len(client.steps),
        "programs_lowered_in_window": counter.lowered,
        "programs_compiled_in_window": counter.compiled,
        "generator_late_p50_ms": 1e3 * float(np.median(late)) if late else None,
        "generator_late_max_ms": 1e3 * max(late) if late else None,
        "preemptions": data.delta("preemptions"),
        "decode_rows": sum(len(st.decode_rows) for st in client.steps),
        "decode_rows_counted": data.delta("occupancy_sum"),
        **data.notes,
    }
    expected = _numbers(mix)
    del engine, client, data
    gc.collect()

    limits = p["limits"]["checks"]
    t = time.perf_counter()
    sample = check.draw_sample(finished, p["limits"]["sample_per_group"], seed)
    # one shape for every run: the longest context and output the mix allows
    seq_len = -(-context_range(mix)[1] // 256) * 256
    pos_len = -(-mix["output"]["max"] // 256) * 256
    got = check.readings(model, seed, sample, seq_len, pos_len) if sample else {}
    info["check_s"] = time.perf_counter() - t
    checks = {name: {"value": got.get(name), "limit": limits[name]["limit"]}
              for name in expected}
    correct = failed == 0 and all(
        c["value"] is not None and c["value"] <= c["limit"] for c in checks.values()
    )
    info["sampled_requests"] = len(sample)
    info["sampled_tokens"] = sum(len(r["served"]) for r in sample)

    result = {
        "correct": bool(correct), "attempted": len(window), "failed": failed,
        "metrics": metrics, "device": device,
    }
    if reduced is not None:
        result["breakdown"] = {
            "device_ops": reduced.top_ops(10),
            "idle_gaps": [[n, s] for n, s in reduced.idle_gaps],
        }
    result["info"] = info
    result["checks"] = checks
    return result


def _numbers(mix: dict) -> list[str]:
    """The names of the numbers the check gives for a traffic mix's groups."""
    from bench.check import number_name
    from bench.traffic import variants

    return sorted({number_name(v["w_bits"], v["kv_bits"], v["temperature"] == 0.0)
                   for v in variants(mix)})


def emit(result: dict, out=sys.stdout, err=sys.stderr) -> None:
    print(json.dumps(result["info"], default=str), file=err)
    for name, c in result["checks"].items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}", file=err)
    err.flush()
    print(json.dumps(result, default=str), file=out)
    out.flush()
