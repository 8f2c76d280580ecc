"""A cell at a size the CPU runs in seconds, for the benchmark's own tests."""
import copy

MODEL = {
    "name": "tiny", "block": "dense", "hidden_size": 128, "intermediate_size": 256,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "num_hidden_layers": 2, "vocab_size": 512, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-6, "hidden_act": "silu",
}

OPEN_MIX = {
    "loop": "open",
    "arrival": {"process": "poisson", "rate_per_s": 8.0},
    "block": 16,
    "roll_forward": {"seconds": 0.5},
    "tenants": [
        {"name": "A", "share": 0.25, "w_bits": 4, "kv_bits": 4, "system_prompt": [20, 40]},
        {"name": "B", "share": 0.5, "w_bits": 8, "kv_bits": 8, "system_prompt": [20, 40],
         "temperature": 0.7, "top_p": 0.9},
        {"name": "C", "share": 0.25, "w_bits": 16, "kv_bits": 8, "system_prompt": [20, 40]},
    ],
    "prompt": {"dist": "lognormal", "median": 12, "sigma": 0.5, "min": 4, "max": 24},
    "output": {"dist": "lognormal", "median": 6, "sigma": 0.5, "min": 3, "max": 10},
    "engine": {"max_slots": 4, "page_size": 8, "prefill_chunk": 8, "num_pages": 128},
}

CLOSED_MIX = {
    "loop": "closed",
    "backlog": 2,
    "block": 8,
    "roll_forward": {"completed": 2, "limit_s": 120},
    "tenants": [{"name": "docqa", "share": 1.0, "w_bits": 8, "kv_bits": 8}],
    "prompt": {"dist": "uniform", "min": 20, "max": 40},
    "output": {"dist": "lognormal", "median": 6, "sigma": 0.5, "min": 3, "max": 10},
    "engine": {"max_slots": 2, "page_size": 8, "prefill_chunk": 16, "num_pages": 64},
}

# three precision groups, one of them sampled, in a closed loop: batching
# follows the steps alone, so a run is the same on every machine
TENANTS_MIX = {
    "loop": "closed",
    "backlog": 2,
    "block": 12,
    "roll_forward": {"completed": 3, "limit_s": 120},
    "tenants": [
        {"name": "A", "share": 1 / 3, "w_bits": 4, "kv_bits": 4, "system_prompt": [16, 24]},
        {"name": "B", "share": 1 / 3, "w_bits": 8, "kv_bits": 8, "system_prompt": [16, 24],
         "temperature": 0.7, "top_p": 0.9},
        {"name": "C", "share": 1 / 3, "w_bits": 16, "kv_bits": 8, "system_prompt": [16, 24]},
    ],
    "prompt": {"dist": "uniform", "min": 4, "max": 12},
    "output": {"dist": "uniform", "min": 4, "max": 8},
    "engine": {"max_slots": 4, "page_size": 8, "prefill_chunk": 8, "num_pages": 96},
}

PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}

# a closed loop's window in engine steps (``harness.run(steps=...)``): what a
# 1.5 s window held on an unloaded CPU (155-165 steps), fixed so that which
# requests finish, and so the check's sample, is the same on any machine
WINDOW_STEPS = 160


# the limit of each number the check compares: (w_bits, kv_bits, greedy)
LIMITS = {"gap_w4kv4": 0.5, "gap_w8kv8": 0.5, "draw_w8kv8": 0.05, "gap_w16kv8": 0.5}


def plan(mix=OPEN_MIX, limits=LIMITS, per_group=2):
    e2e = [
        {"name": n, "unit": u} for n, u in
        (("tok_s", "tokens/s"), ("setup_s", "s"))
    ]
    per_layer = [
        {"name": n, "unit": u} for n, u in
        (("ttft_p50_ms.open_loop", "ms"), ("queue_wait_p50_ms", "ms"),
         ("prefix_hit_rate", "%"), ("itl_p99_ms.saturated", "ms"))
    ]
    return {
        "workload": "tiny", "chips": 1, "model": dict(MODEL),
        "mix": copy.deepcopy(mix), "peaks": PEAKS,
        "limits": {"sample_per_group": per_group,
                   "control": {"w8kv8": "w4kv8", "w16kv8": "w16kv4"},
                   "checks": {k: {"limit": v} for k, v in limits.items()}},
        "end_to_end": e2e, "per_layer": per_layer,
    }
