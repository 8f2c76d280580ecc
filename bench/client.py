"""The client loop: submits requests when due, drives ``ServeEngine.step()``
and records, per request, when each output token came out.

Open loop: requests are submitted once their due time has passed, whether
or not earlier ones finished.  Closed loop: the client keeps ``max_slots +
backlog`` requests in flight, so a backlog waits through the whole window.
Every call into the engine runs inside a host span (``submit``,
``engine.step``, ``await-arrival``) that the trace reduction labels idle
device time with.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Iterator, Optional

import jax

from bench.traffic import RequestSpec


@dataclass
class Flight:
    spec: RequestSpec
    due_t: float
    req: object = None  # the engine's ServeRequest
    submit_t: float = 0.0
    token_t: list = field(default_factory=list)
    admitted_t: Optional[float] = None  # end of the first step it left WAITING
    failed: bool = False
    error: str = ""

    @property
    def done(self) -> bool:
        return self.failed or (self.req is not None and (self.req.done or self.req.failed))


@dataclass
class StepLog:
    t0: float
    t1: float
    decode_rows: list  # (w_bits, kv_bits, ctx)
    waiting: int  # requests due and not yet admitted, after the step


class Client:
    def __init__(self, engine, source: Iterator[RequestSpec], *, closed: bool,
                 in_flight: int = 0):
        from repro.serve.params import PrecisionParams, SamplingParams
        from repro.serve.request import RequestState

        self._Sampling, self._Precision = SamplingParams, PrecisionParams
        self._WAITING = RequestState.WAITING
        self.engine = engine
        self.source = source
        self.closed = closed
        self.in_flight_target = in_flight
        self.origin = 0.0
        self.next_spec: Optional[RequestSpec] = None
        self.flights: list[Flight] = []
        self.live: list[Flight] = []
        self.steps: list[StepLog] = []
        self.record = False
        self.serve_as = {}  # (w_bits, kv_bits) -> served pair (the control's lower path)

    # ------------------------------------------------------------ submit
    def _submit(self, spec: RequestSpec, due_t: float) -> None:
        f = Flight(spec=spec, due_t=due_t)
        w_bits, kv_bits = self.serve_as.get(
            (spec.w_bits, spec.kv_bits), (spec.w_bits, spec.kv_bits))
        with jax.profiler.TraceAnnotation("submit"):
            f.submit_t = time.perf_counter()
            try:
                f.req = self.engine.submit(
                    spec.prompt,
                    self._Sampling(
                        temperature=spec.temperature, top_p=spec.top_p,
                        seed=spec.seed, max_new_tokens=spec.max_new_tokens,
                    ),
                    self._Precision(w_bits=w_bits, kv_bits=kv_bits),
                )
            except ValueError as e:  # the engine refused it
                f.failed, f.error = True, str(e)
        self.flights.append(f)
        if not f.failed:
            self.live.append(f)

    def _peek(self) -> RequestSpec:
        if self.next_spec is None:
            self.next_spec = next(self.source)
        return self.next_spec

    def _submit_due(self, now: float) -> None:
        if self.closed:
            while len(self.live) < self.in_flight_target:
                self._submit(self._peek(), now)
                self.next_spec = None
            return
        while self.origin + self._peek().due <= now:
            spec = self._peek()
            self.next_spec = None
            self._submit(spec, self.origin + spec.due)

    # -------------------------------------------------------------- step
    def _step(self) -> None:
        eng = self.engine
        before = {
            id(f): (f.req.cache_len, len(f.req.out_tokens),
                    f.req.state is self._WAITING)
            for f in self.live
        }
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("engine.step"):
            eng.step()
        t1 = time.perf_counter()
        decode_rows = []
        for f in self.live:
            r = f.req
            c0, n0, waiting0 = before[id(f)]
            c1, n1 = r.cache_len, len(r.out_tokens)
            f.token_t.extend([t1] * (n1 - len(f.token_t)))
            if f.admitted_t is None and r.state is not self._WAITING:
                f.admitted_t = t1
            if not self.record:
                continue
            prefilling0 = waiting0 or c0 < len(r.prompt)
            first = 1 if (prefilling0 and n0 == 0 and n1 > 0) else 0
            if n1 - n0 - first > 0:
                decode_rows.append((r.w_bits, r.kv_bits, c1 - 1))
        if self.record:
            waiting = sum(1 for f in self.live if f.admitted_t is None)
            self.steps.append(StepLog(t0, t1, decode_rows, waiting))
        self.live = [f for f in self.live if not f.done]

    # -------------------------------------------------------------- loop
    def run_until(self, t_end: float) -> None:
        while True:
            now = time.perf_counter()
            if now >= t_end:
                return
            self._submit_due(now)
            if self.live:
                self._step()
                continue
            wake = min(self.origin + self._peek().due, t_end)
            with jax.profiler.TraceAnnotation("await-arrival"):
                time.sleep(max(wake - time.perf_counter(), 0.0))

    def run_steps(self, n: int) -> None:
        """Closed loop: exactly ``n`` engine steps, whatever the clock reads."""
        for _ in range(n):
            self._submit_due(time.perf_counter())
            self._step()

    def run_until_completed(self, n: int, t_limit: float) -> None:
        """Closed loop roll-forward: step until ``n`` requests completed."""
        while sum(1 for f in self.flights if f.done) < n:
            if time.perf_counter() > t_limit:
                raise TimeoutError(f"roll-forward did not complete {n} requests")
            self._submit_due(time.perf_counter())
            self._step()

    def drain(self, t_limit: float) -> None:
        """Step without new submissions until every request is done."""
        while self.live and time.perf_counter() < t_limit:
            self._step()
