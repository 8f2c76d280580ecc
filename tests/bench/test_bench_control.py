"""The check's control at a size the CPU holds: the same run with requests
served on the program's own next-lower paths (W8/kv8 -> W4/kv8 for the
sampled group, bf16/kv8 -> bf16/kv4 for a greedy one) reads past the limits,
and the sound run does not.

Readings that set these tiny limits (CPU, 1.5 s windows, seeds 11, 7, 99,
4242, 12345, 31337, 2**31 + 2, 2**31 + 3, 2**31 + 41, 2**31 + 52,
2**31 + 352), sound then control: ``draw_w8kv8`` 0-0.0038 against
0.0165-0.0576; ``gap_w16kv8`` 0-0.0166 against 0.0801-0.2709 (0.093 once
under a loaded CPU, where fewer requests finish in the window);
``gap_w4kv4`` 0-0.2324 (no lower path: the control leaves it as it is).
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
        control=control,
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
