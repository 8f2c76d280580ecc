"""Metric arithmetic and the peaks table."""
import math

import pytest

from bench.client import Flight
from bench.harness import reader
from bench.measure import RunData, percentile, samples_beyond
from bench.peaks import peaks_for

M = {
    "hidden_size": 4096, "intermediate_size": 11008, "num_attention_heads": 32,
    "num_key_value_heads": 4, "head_dim": 128, "num_hidden_layers": 16,
    "vocab_size": 64000,
}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


class _Req:
    def __init__(self, failed=False):
        self.failed = failed
        self.done = not failed


def _flight(due, tokens=(), failed=False, admitted=None):
    f = Flight(spec=None, due_t=due, req=_Req(failed))
    f.token_t = list(tokens)
    f.admitted_t = admitted
    return f


def _run(flights, steps=(), closed=False, stats0=None, stats1=None, trace=None):
    return RunData(
        model=M, mix={}, peaks=PEAKS, closed=closed, t0=10.0, t1=20.0, setup_s=3.5,
        flights=list(flights), steps=list(steps), stats0=stats0 or {},
        stats1=stats1 or {}, trace=trace,
    )


def test_percentile_is_nearest_rank():
    vals = list(range(1, 101))
    assert percentile(vals, 90) == 90
    assert percentile(vals, 99) == 99
    assert percentile([5.0], 90) == 5.0
    assert samples_beyond(100, 90) == 10
    with pytest.raises(ValueError):
        percentile([], 50)


def test_unfinished_and_failed_requests_enter_the_ttft_tail():
    done = [_flight(10.0 + i * 0.1, tokens=[10.0 + i * 0.1 + 0.2]) for i in range(2)]
    waiting = _flight(11.0)  # due at 11, no token by the end (20): 9 s so far
    failed = _flight(12.0, failed=True)
    late = _flight(19.0, tokens=[21.0])  # first token after the window: 1 s so far
    before = _flight(9.0, tokens=[9.5])  # due before the window: not counted
    run = _run(done + [waiting, failed, late, before])
    # 5 requests due in the window: 0.2, 0.2, 1 (late), 9 (waiting), inf (failed)
    assert reader("ttft_p50_ms.open_loop")(run) == pytest.approx(1000.0)
    assert run.notes["ttft_requests"] == 5 and run.notes["ttft_beyond_p50"] == 2
    assert run.notes["ttft_p80_ms"] == pytest.approx(9000.0)
    assert run.notes["ttft_p90_ms"] == math.inf
    assert reader("ttft_p50_ms.open_loop")(_run(done, closed=True)) is None


def test_queue_wait_counts_requests_still_waiting():
    fl = [_flight(10.0 + i, admitted=10.5 + i) for i in range(2)]
    fl += [_flight(17.0), _flight(18.0), _flight(19.0)]  # waiting 3, 2, 1 s
    assert reader("queue_wait_p50_ms")(_run(fl)) == pytest.approx(1000.0)


def test_itl_tok_s_and_setup():
    a = _flight(5.0, tokens=[9.0, 10.5, 11.0, 11.0, 20.5])  # gaps closing in window: 1.5, .5, 0
    b = _flight(12.0, tokens=[13.0, 15.0])
    run = _run([a, b])
    assert reader("itl_p99_ms.saturated")(run) == pytest.approx(2000.0)
    assert run.notes["itl_gaps"] == 4
    # tokens at 10.5, 11, 11 (a) and 13, 15 (b) inside (10, 20]: 5 over 10 s
    assert reader("tok_s")(run) == pytest.approx(0.5)
    assert run.notes["window_tokens"] == 5
    assert reader("tok_s")(_run([_flight(5.0, tokens=[9.0, 20.5])])) is None
    assert reader("setup_s")(run) == 3.5


def test_counter_metrics_are_taken_as_changes_over_the_window():
    s0 = {"decode_steps": 100, "occupancy_sum": 300, "prefix_hit_tokens": 1000,
          "prefix_new_tokens": 1000}
    s1 = {"decode_steps": 110, "occupancy_sum": 380, "prefix_hit_tokens": 1600,
          "prefix_new_tokens": 1200}
    run = _run([], stats0=s0, stats1=s1)
    assert reader("prefix_hit_rate")(run) == pytest.approx(75.0)
    none = _run([], stats0=s0, stats1=s0)
    assert reader("prefix_hit_rate")(none) is None


def test_unknown_device_kind_is_an_error():
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")
    assert math.isfinite(peaks_for("TPU v5 lite")["bf16_flops_per_s"])
