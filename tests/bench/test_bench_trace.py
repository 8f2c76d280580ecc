"""Trace reduction on a small recorded device trace: the first quarter
second of a traced window of ``yi-9b.tenants.poisson`` on one TPU v5e, cut
from the profiler's planes and kept as JSON (the device's ``XLA Ops`` line,
each op named by its HLO instruction without the operand text, and the
harness's host spans)."""
import json
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import devtrace

FIXTURE = Path(__file__).with_name("fixtures") / "trace_window.json"


def _planes(doc):
    return [
        NS(name=p["name"], lines=[
            NS(name=ln["name"], events=[NS(name=n, start_ns=s, duration_ns=d)
                                        for n, s, d in ln["events"]])
            for ln in p["lines"]
        ])
        for p in doc["planes"]
    ]


@pytest.fixture(scope="module")
def doc():
    return json.loads(FIXTURE.read_text())


def _window(doc):
    for p in doc["planes"]:
        for ln in p["lines"]:
            for n, s, d in ln["events"]:
                if n == devtrace.WINDOW_SPAN:
                    return s, s + d
    raise AssertionError("fixture has no window span")


def _device_ops(doc):
    return [ln["events"] for p in doc["planes"] if p["name"].startswith("/device:TPU:")
            for ln in p["lines"] if ln["name"] == devtrace.OPS_LINE]


def test_busy_and_idle_share_of_the_recorded_window(doc):
    w0, w1 = _window(doc)
    red = devtrace.reduce_planes(_planes(doc))
    assert red.window_s == pytest.approx((w1 - w0) * 1e-9)
    # busy time by a sweep over start/end points, independent of the reduction
    busy = []
    for events in _device_ops(doc):
        points = sorted(
            [(max(s, w0), 1) for n, s, d in events if min(s + d, w1) > max(s, w0)]
            + [(min(s + d, w1), -1) for n, s, d in events if min(s + d, w1) > max(s, w0)]
        )
        depth, last, total = 0, None, 0.0
        for t, step in points:
            if depth > 0:
                total += t - last
            depth += step
            last = t
        busy.append(total * 1e-9)
    assert red.n_devices == len(busy) >= 1
    assert red.busy_s == pytest.approx(sum(busy) / len(busy), rel=1e-9)
    assert 0.0 < red.busy_s <= red.window_s


def test_per_kernel_time_sums_the_named_events(doc):
    w0, w1 = _window(doc)
    red = devtrace.reduce_planes(_planes(doc))
    for prefix in ("paged_mqa_decode_kv", "paged_mqa_prefill_kv"):
        want = sum(
            (min(s + d, w1) - max(s, w0)) * 1e-9
            for events in _device_ops(doc) for n, s, d in events
            if n.lstrip("%").startswith(prefix) and min(s + d, w1) > max(s, w0)
        ) / len(_device_ops(doc))
        assert red.time_of(prefix) == pytest.approx(want, rel=1e-9)
    assert red.time_of("paged_mqa_decode_kv") > 0
    top = red.top_ops(10)
    assert len(top) <= 10 and all(a[1] >= b[1] for a, b in zip(top, top[1:]))


def test_idle_gaps_carry_the_host_span_they_fall_in(doc):
    red = devtrace.reduce_planes(_planes(doc))
    labels = {n for n, _ in red.idle_gaps}
    assert labels <= set(devtrace.HOST_SPANS) | {"other"}
    gaps = [s for _, s in red.idle_gaps]
    assert gaps == sorted(gaps, reverse=True)
    assert sum(gaps) <= red.window_s - red.busy_s + 1e-12


def test_a_trace_without_the_window_span_is_refused(doc):
    planes = _planes({"planes": [
        {"name": p["name"], "lines": [
            {"name": ln["name"], "events": [e for e in ln["events"]
                                            if e[0] != devtrace.WINDOW_SPAN]}
            for ln in p["lines"]]}
        for p in doc["planes"]]})
    with pytest.raises(ValueError):
        devtrace.reduce_planes(planes)


def test_op_names_drop_hlo_suffixes():
    assert devtrace.op_name(
        "%paged_mqa_decode_kv8.5 = f32[8,4,1,8,128]{4,3,2,1,0} custom-call(s32[8,128] %x)"
    ) == "paged_mqa_decode_kv8"
    assert devtrace.op_name("paged_mqa_decode_kv8.7") == "paged_mqa_decode_kv8"
    assert devtrace.op_name("fusion.123") == "fusion"
    assert devtrace.op_name("copy-start") == "copy-start"
