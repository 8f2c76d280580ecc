"""The check's control at a size the CPU holds: the same run with requests
served on the program's own next-lower paths (W8/kv8 -> W4/kv8 for the
sampled group, bf16/kv8 -> bf16/kv4 for a greedy one) reads past the limits,
and the sound run does not.

Readings that set these tiny limits (CPU, windows of ``tiny.WINDOW_STEPS``
engine steps, seeds 11, 7, 99, 4242, 12345, 31337, 2**31 + 2, 2**31 + 3,
2**31 + 41, 2**31 + 52, 2**31 + 352), sound then control: ``draw_w8kv8``
0-0.0032 against 0.0197-0.0576; ``gap_w16kv8`` 0-0.0307 against
0.0849-0.2551; ``gap_w4kv4`` 0-0.2364 (no lower path: the control leaves it
as it is).  A window of wall-clock seconds held as many steps as the machine
managed, so which requests finished: at 141 steps seed 2**31 + 41's control
read ``gap_w16kv8`` 0.0375, at 160 steps 0.116.
"""
import time

import pytest

import tiny
from bench import harness

LIMITS = {"gap_w4kv4": 0.3, "draw_w8kv8": 0.012, "gap_w16kv8": 0.045}


@pytest.mark.parametrize("control", [False, True], ids=["sound", "control"])
def test_control_fails_the_check_and_the_sound_run_passes(control):
    r = harness.run(
        tiny.plan(tiny.TENANTS_MIX, limits=LIMITS, per_group=3), seed=2**31 + 41,
        seconds=1.5, trace=False, t_start=time.perf_counter(), require_tpu=False,
        control=control, steps=tiny.WINDOW_STEPS,
    )
    assert r["correct"] is (not control), r["info"]
    assert list(r)[-1] == "checks"
    assert {k: c["limit"] for k, c in r["checks"].items()} == LIMITS
    if control:  # both lowered paths read past their limits
        assert r["checks"]["draw_w8kv8"]["value"] > LIMITS["draw_w8kv8"]
        assert r["checks"]["gap_w16kv8"]["value"] > LIMITS["gap_w16kv8"]
    assert r["info"]["sampled_requests"] == 9
    assert r["failed"] == 0 and r["attempted"] > 0
    # the client's per-step accounting of decode rows matches the engine's count
    assert r["info"]["decode_rows"] == r["info"]["decode_rows_counted"] > 0


def test_a_closed_loop_multi_head_cell_serves_correctly():
    """A closed loop with a backlog at one query head a KV head, W8/kv8
    greedy: the multi-head block through the whole harness."""
    p = tiny.plan(tiny.CLOSED_MIX, limits={"gap_w8kv8": 0.5}, per_group=2)
    p["model"]["num_key_value_heads"] = p["model"]["num_attention_heads"]
    r = harness.run(p, seed=2**31 + 47, seconds=1.5, trace=False,
                    t_start=time.perf_counter(), require_tpu=False,
                    steps=tiny.WINDOW_STEPS)
    assert r["correct"] is True, (r["checks"], r["info"])
    assert r["failed"] == 0 and r["metrics"]["tok_s"]["value"] > 0
    assert r["info"]["sampled_requests"] == 2
