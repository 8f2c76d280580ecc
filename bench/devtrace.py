"""Profiler trace of the measured window, reduced to device busy time,
per-kernel device time and idle gaps labelled by the harness's host spans.

Device planes are named ``/device:TPU:<n>``; their op events sit on the line
``XLA Ops``.  The harness wraps the window in the host span ``bench.window``
and each call into the engine in ``submit``, ``engine.step`` or
``await-arrival``; every event of one trace shares one clock.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

WINDOW_SPAN = "bench.window"
HOST_SPANS = ("engine.step", "submit", "await-arrival")
OPS_LINE = "XLA Ops"


@dataclass
class Reduced:
    window_ns: tuple[float, float]
    busy_s: float  # union of op intervals, averaged over devices
    window_s: float
    kernel_s: dict[str, float] = field(default_factory=dict)  # op name -> s
    idle_gaps: list[tuple[str, float]] = field(default_factory=list)
    n_devices: int = 0

    def time_of(self, prefix: str) -> float:
        """Summed device seconds of ops whose name starts with ``prefix``."""
        return sum(s for name, s in self.kernel_s.items() if name.startswith(prefix))

    def top_ops(self, n: int = 10) -> list[list]:
        ranked = sorted(self.kernel_s.items(), key=lambda kv: -kv[1])[:n]
        return [[name, s] for name, s in ranked]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def op_name(name: str) -> str:
    """``%paged_mqa_decode_kv8.5 = f32[...] custom-call(...)`` ->
    ``paged_mqa_decode_kv8``: a TPU trace names each op event by its HLO
    instruction, with the numbered suffix XLA gives it."""
    head = name.split(" = ", 1)[0].lstrip("%")
    base, _, tail = head.rpartition(".")
    return base if base and tail.isdigit() else head


def reduce_planes(planes, n_gaps: int = 10) -> Reduced:
    """``planes``: objects with ``name`` and ``lines``; a line has ``name`` and
    ``events``; an event has ``name``, ``start_ns`` and ``duration_ns``
    (``jax.profiler.ProfileData`` planes, or the same shape from a fixture)."""
    window = None
    spans: list[tuple[float, float, str]] = []
    devices = []
    for plane in planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif ev.name in HOST_SPANS:
                        spans.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
        elif plane.name.startswith("/device:TPU:") or plane.name.startswith("/device:CPU:"):
            ops = [ln for ln in plane.lines if ln.name == OPS_LINE]
            if ops:
                devices.append(ops[0])
    if window is None:
        raise ValueError(f"trace has no host span {WINDOW_SPAN!r}")
    if not devices:
        raise ValueError(f"trace has no device plane with a {OPS_LINE!r} line")
    w0, w1 = window
    busy_total = 0.0
    kernel_s: dict[str, float] = {}
    first_union = None
    for line in devices:
        iv = []
        for ev in line.events:
            s = max(ev.start_ns, w0)
            e = min(ev.start_ns + ev.duration_ns, w1)
            if e <= s:
                continue
            iv.append((s, e))
            key = op_name(ev.name)
            kernel_s[key] = kernel_s.get(key, 0.0) + (e - s) * 1e-9
        union = _union(iv)
        busy_total += sum(e - s for s, e in union)
        if first_union is None:
            first_union = union
    n_dev = len(devices)
    kernel_s = {k: v / n_dev for k, v in kernel_s.items()}
    gaps = []
    prev = w0
    for s, e in first_union + [(w1, w1)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    labelled = []
    for g0, g1 in gaps[:n_gaps]:
        mid = 0.5 * (g0 + g1)
        label = next((n for s, e, n in spans if s <= mid <= e), "other")
        labelled.append((label, (g1 - g0) * 1e-9))
    return Reduced(
        window_ns=window,
        busy_s=busy_total * 1e-9 / n_dev,
        window_s=(w1 - w0) * 1e-9,
        kernel_s=kernel_s,
        idle_gaps=labelled,
        n_devices=n_dev,
    )


def reduce_dir(trace_dir: str) -> Reduced:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(find_xplane(trace_dir)).planes)
