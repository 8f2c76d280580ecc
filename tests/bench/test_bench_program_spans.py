"""The readers of the program's own spans and counters (``step_host_ms``,
``decode_call_ms``, ``chunk_call_ms``, ``admit_wait_p50_ms``) on synthetic
runs, and on a program that has none of them."""
import pytest

from bench.client import Flight
from bench.harness import reader
from bench.measure import RunData

S0 = {"engine_steps": 100, "step_s": 20.0, "decode_wait_s": 12.0, "chunk_wait_s": 3.0,
      "decode_steps": 300, "prefill_chunks": 50}
S1 = {"engine_steps": 110, "step_s": 21.5, "decode_wait_s": 12.9, "chunk_wait_s": 3.4,
      "decode_steps": 330, "prefill_chunks": 58}
OLD = {"engine_steps": 100, "decode_steps": 300, "prefill_chunks": 50}  # no span counters


class _Req:
    def __init__(self, submit_ts, admitted_ts, failed=False):
        self.submit_ts = submit_ts
        self.admitted_ts = admitted_ts
        self.failed = failed
        self.done = not failed


class _OldReq:  # a request of a program that does not stamp admission
    submit_ts = 10.0
    failed = False
    done = True


def _flight(due, req, failed=False):
    f = Flight(spec=None, due_t=due, req=req)
    f.failed = failed
    return f


def _run(flights=(), stats0=S0, stats1=S1, closed=False):
    return RunData(
        model={}, mix={}, peaks={}, closed=closed, t0=10.0, t1=20.0, setup_s=1.0,
        flights=list(flights), steps=[], stats0=dict(stats0), stats1=dict(stats1),
    )


def test_step_host_time_is_the_step_less_its_chip_calls():
    run = _run()
    # (1.5 - 0.9 - 0.4) s over 10 steps
    assert reader("step_host_ms")(run) == pytest.approx(20.0)
    assert run.notes["window_steps_counted"] == 10


def test_call_times_are_means_per_call():
    run = _run()
    assert reader("decode_call_ms")(run) == pytest.approx(30.0)  # 0.9 s / 30 calls
    assert reader("chunk_call_ms")(run) == pytest.approx(50.0)  # 0.4 s / 8 calls
    assert run.notes["decode_calls"] == 30 and run.notes["chunk_calls"] == 8


@pytest.mark.parametrize("metric", ["step_host_ms", "decode_call_ms", "chunk_call_ms"])
def test_an_empty_denominator_reads_nothing(metric):
    assert reader(metric)(_run(stats1=S0)) is None


def test_a_window_without_prefill_reads_no_chunk_time_but_decode_time():
    s1 = {**S1, "prefill_chunks": S0["prefill_chunks"], "chunk_wait_s": S0["chunk_wait_s"]}
    run = _run(stats1=s1)
    assert reader("chunk_call_ms")(run) is None
    assert reader("decode_call_ms")(run) == pytest.approx(30.0)


@pytest.mark.parametrize(
    "metric", ["step_host_ms", "decode_call_ms", "chunk_call_ms", "admit_wait_p50_ms"])
def test_a_program_without_the_spans_reads_nothing(metric):
    run = _run([_flight(11.0, _OldReq())], stats0=OLD, stats1=OLD)
    assert reader(metric)(run) is None


def test_admit_wait_counts_requests_still_waiting_at_the_window_end():
    fl = [_flight(10.0 + i, _Req(10.0 + i, 10.1 + i)) for i in range(2)]  # 0.1 s each
    fl.append(_flight(16.0, _Req(16.0, 21.0)))  # admitted after the end: 4 s so far
    fl += [_flight(17.0, _Req(17.0, None)), _flight(18.0, _Req(18.0, None))]  # 3, 2 s
    fl.append(_flight(12.0, _Req(12.0, None, failed=True)))  # failed: not counted
    fl.append(_flight(13.0, None, failed=True))  # refused at submit: not counted
    fl.append(_flight(9.0, _Req(9.0, 9.5)))  # due before the window: not counted
    run = _run(fl)
    assert reader("admit_wait_p50_ms")(run) == pytest.approx(2000.0)
    assert run.notes["admit_samples"] == 5


def test_admit_wait_measures_from_submission_not_due_time():
    late = _flight(11.0, _Req(11.5, 11.6))  # submitted 0.5 s after it was due
    assert reader("admit_wait_p50_ms")(_run([late])) == pytest.approx(100.0)
    assert reader("admit_wait_p50_ms")(_run([])) is None
