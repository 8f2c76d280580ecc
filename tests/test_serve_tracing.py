"""Host spans and counters of ServeEngine.step(): every span of the engine's
table appears in a CPU profiler trace with its nesting and args, the step
programs carry their names, the counters add up, admission is stamped once,
and tracing leaves the token streams as they were."""
import dataclasses
import glob
import json
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.kernels import mpmm as mpmm_mod
from repro.models import layers
from repro.models import transformer as T
from repro.serve import PrecisionParams, SamplingParams, ServeEngine
from repro.serve.engine import _shared_jits

GROUP_ARGS = {"w_bits", "kv_bits", "rows", "bsz", "width", "rids"}
ARGS = {
    "serve.step": {"step"},
    "serve.admit": {"admitted", "waiting", "rids"},
    "serve.prefill": {"groups"},
    "serve.chunk": {"w_bits", "kv_bits", "rows", "chunk", "width", "rids"},
    "serve.page_room": {"preempted"},
    "serve.decode": {"groups"},
    "serve.decode_group": GROUP_ARGS,
    "serve.spec_group": GROUP_ARGS | {"draft_bits"},
    "serve.inputs": set(),
    "serve.call": {"program", "mpmm"},
    "serve.outputs": set(),
}
GROUPS = ("serve.chunk", "serve.decode_group", "serve.spec_group")
PARENT = {
    "serve.step": None,
    "serve.admit": "serve.step",
    "serve.prefill": "serve.step",
    "serve.page_room": "serve.step",
    "serve.decode": "serve.step",
    "serve.chunk": "serve.prefill",
    "serve.decode_group": "serve.decode",
    "serve.spec_group": "serve.decode",
    "serve.inputs": GROUPS,
    "serve.call": GROUPS,
    "serve.outputs": GROUPS,
}
PROGRAM = {"serve.chunk": "serve_chunk", "serve.decode_group": "serve_decode",
           "serve.spec_group": "serve_spec"}


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(
        get_config("llama3.2-3b").reduced(),
        n_layers=2, d_model=64, d_ff=128, vocab=256, n_heads=4, n_kv_heads=2,
        head_dim=16, serve_kv_bits=8,
    )
    return cfg, T.init_params(cfg, jax.random.PRNGKey(0))


def _submit_mix(eng, vocab):
    """W4/kv4, W8/kv8 sampled, bf16/kv8 greedy, and one bf16 request
    speculating with a W8 draft; prompts of two chunks or more."""
    rng = np.random.default_rng(5)
    mix = [
        (SamplingParams(max_new_tokens=6), PrecisionParams(w_bits=4, kv_bits=4)),
        (SamplingParams(max_new_tokens=6, temperature=0.7, top_p=0.9, seed=11),
         PrecisionParams(w_bits=8, kv_bits=8)),
        (SamplingParams(max_new_tokens=6), PrecisionParams(w_bits=16, kv_bits=8)),
        (SamplingParams(max_new_tokens=6),
         PrecisionParams(w_bits=16, kv_bits=8, spec_k=2, draft_bits=8)),
    ]
    return [
        eng.submit(rng.integers(0, vocab, 12 + 3 * i).astype(np.int32), s, p)
        for i, (s, p) in enumerate(mix)
    ]


def _engine(setup, **kw):
    cfg, params = setup
    kw = {"max_slots": 4, "num_pages": 64, "page_size": 4, "prefill_chunk": 8, **kw}
    return ServeEngine(cfg, params, **kw)


def _drive(eng):
    """Step to the end; per request, the end of the step that gave its
    first token."""
    first_t = {}
    while eng._sched.has_work():
        eng.step()
        now = time.perf_counter()
        for r in eng._sched.running + eng.finished:
            if r.out_tokens and r.rid not in first_t:
                first_t[r.rid] = now
    return first_t


def _traced(tmp_path, fn):
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events += [
                    (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name, dict(ev.stats))
                    for ev in line.events
                    if ev.name.startswith(("serve.", "PjitFunction(serve_"))
                ]
    return out, events


def _parent(ev, spans):
    """The innermost span that holds ``ev``."""
    s0, s1 = ev[0], ev[1]
    holders = [p for p in spans if p is not ev and p[0] <= s0 and s1 <= p[1]]
    return min(holders, key=lambda p: p[1] - p[0]) if holders else None


@pytest.fixture(scope="module")
def traced_mix(setup, tmp_path_factory):
    eng = _engine(setup)
    _submit_mix(eng, setup[0].vocab)
    _drive(eng)  # compiles every program outside the trace
    eng = _engine(setup)
    reqs = _submit_mix(eng, setup[0].vocab)
    first_t, events = _traced(tmp_path_factory.mktemp("trace"), lambda: _drive(eng))
    return eng, reqs, first_t, events


def test_every_span_appears_with_its_args_and_nesting(traced_mix):
    eng, reqs, _, events = traced_mix
    spans = [e for e in events if e[2].startswith("serve.")]
    assert {e[2] for e in spans} == set(ARGS)
    for ev in spans:
        assert set(ev[3]) == ARGS[ev[2]], ev
        parent = _parent(ev, spans)
        want = PARENT[ev[2]]
        if want is None:
            assert parent is None
        else:
            assert parent is not None and parent[2] in (
                want if isinstance(want, tuple) else (want,)), (ev, parent)
    steps = [e for e in spans if e[2] == "serve.step"]
    assert [e[3]["step"] for e in steps] == list(range(eng.stats.engine_steps))
    for group in (e for e in spans if e[2] in GROUPS):
        kids = sorted((e for e in spans if _parent(e, spans) is group), key=lambda e: e[0])
        assert [e[2] for e in kids] == ["serve.inputs", "serve.call", "serve.outputs"]
        assert kids[1][3]["program"] == PROGRAM[group[2]]
        assert group[3]["rows"] == len(json.loads(group[3]["rids"]))
    # the spans' own counts match the engine's
    decode_groups = [e for e in spans if e[2] in ("serve.decode_group", "serve.spec_group")]
    assert len(decode_groups) == eng.stats.decode_steps
    assert sum(e[2] == "serve.chunk" for e in spans) == eng.stats.prefill_chunks
    assert {e[3]["w_bits"] for e in decode_groups} == {4, 8, 16}
    spec = [e for e in spans if e[2] == "serve.spec_group"]
    assert spec and {(e[3]["w_bits"], e[3]["draft_bits"]) for e in spec} == {(16, 8)}
    admitted = [rid for e in spans if e[2] == "serve.admit" for rid in json.loads(e[3]["rids"])]
    assert sorted(admitted) == sorted(r.rid for r in reqs)


def test_dispatches_carry_the_program_names(traced_mix):
    _, _, _, events = traced_mix
    calls = [e for e in events if e[2] == "serve.call"]
    for name in ("serve_decode", "serve_chunk", "serve_spec"):
        dispatches = [e for e in events if e[2] == f"PjitFunction({name})"]
        assert dispatches, name
        for d in dispatches:  # each inside a serve.call of its own program
            holder = _parent(d, calls)
            assert holder is not None and holder[3]["program"] == name
    names = [f.__name__ for f in _shared_jits()]
    assert names == ["serve_prefill", "serve_decode", "serve_chunk", "serve_spec"]


def _call_paths(events):
    """(w_bits, draft_bits, mpmm) of each serve.call, from its group span."""
    spans = [e for e in events if e[2].startswith("serve.")]
    out = []
    for call in (e for e in spans if e[2] == "serve.call"):
        group = _parent(call, spans)
        out.append((group[3]["w_bits"], group[3].get("draft_bits"), call[3]["mpmm"]))
    return out


def test_serve_call_names_what_the_projections_run_on(traced_mix):
    """Off a TPU the quantized projections run the XLA reference, bf16 ones
    nothing of mpmm, and the kernel counter stays at zero."""
    eng, _, _, events = traced_mix
    paths = _call_paths(events)
    assert {p for _, _, p in paths} == {"xla", "none"}
    for w_bits, draft_bits, path in paths:
        quantized = w_bits < 16 or (draft_bits or 16) < 16
        assert path == ("xla" if quantized else "none")
    assert eng.stats.mpmm_kernel_calls == 0


def test_counters_split_the_step_into_host_work_and_chip_calls(traced_mix):
    eng, _, _, _ = traced_mix
    s = eng.stats
    assert s.decode_wait_s > 0 and s.chunk_wait_s > 0
    assert s.step_s >= s.decode_wait_s + s.chunk_wait_s


def test_admission_is_stamped_between_submit_and_first_token(traced_mix):
    _, reqs, first_t, _ = traced_mix
    for r in reqs:
        assert r.done and r.admitted_ts is not None
        assert r.submit_ts <= r.admitted_ts <= first_t[r.rid]


def test_a_readmission_keeps_the_first_admission(setup, tmp_path):
    eng = _engine(setup, max_slots=3, num_pages=4)
    rng = np.random.default_rng(3)
    reqs = [
        eng.submit(rng.integers(0, setup[0].vocab, 6).astype(np.int32),
                   SamplingParams(max_new_tokens=8), PrecisionParams(w_bits=8))
        for _ in range(3)
    ]
    first: dict[int, float] = {}

    def drive():
        while eng._sched.has_work():
            eng.step()
            for r in reqs:
                if r.admitted_ts is not None:
                    assert first.setdefault(r.rid, r.admitted_ts) == r.admitted_ts

    _, events = _traced(tmp_path, drive)
    assert eng.stats.preemptions > 0 and any(r.preemptions for r in reqs)
    assert all(r.done for r in reqs) and set(first) == {r.rid for r in reqs}
    preempted = sum(e[3]["preempted"] for e in events if e[2] == "serve.page_room")
    assert preempted == eng.stats.preemptions


def test_streams_are_the_same_with_the_profiler_on_and_off(setup, traced_mix):
    _, traced, _, _ = traced_mix
    eng = _engine(setup)
    plain = _submit_mix(eng, setup[0].vocab)
    _drive(eng)
    assert [r.out_tokens for r in plain] == [r.out_tokens for r in traced]
    assert all(len(r.out_tokens) == 6 for r in plain)


def test_the_legacy_prefill_path_opens_its_spans(tmp_path):
    cfg = dataclasses.replace(
        get_config("paligemma-3b").reduced(),
        n_layers=2, d_model=64, d_ff=128, vocab=256, n_heads=2, n_kv_heads=1,
        head_dim=32, prefix_len=4,
    )
    eng = ServeEngine(cfg, T.init_params(cfg, jax.random.PRNGKey(0)), max_slots=2,
                      num_pages=16, page_size=4)
    rng = np.random.default_rng(6)
    reqs = [eng.submit(rng.integers(0, cfg.vocab, 6).astype(np.int32),
                       SamplingParams(max_new_tokens=3)) for _ in range(2)]
    _, events = _traced(tmp_path, lambda: _drive(eng))
    assert all(r.done and r.admitted_ts is not None for r in reqs)
    spans = [e for e in events if e[2].startswith("serve.")]
    call = [e for e in spans if e[2] == "serve.call" and e[3]["program"] == "serve_prefill"]
    assert len(call) == 1 and _parent(call[0], spans)[2] == "serve.prefill"
    assert _parent(_parent(call[0], spans), spans)[2] == "serve.step"
    assert any(e[2] == "PjitFunction(serve_prefill)" for e in events)
    assert {e[2] for e in spans} >= {"serve.step", "serve.admit", "serve.prefill",
                                     "serve.decode", "serve.decode_group"}


def test_the_mpmm_kernel_runs_in_every_quantized_call_and_no_other(
    setup, tmp_path, monkeypatch
):
    """Where the dispatch rule picks the kernel (as on a TPU with no mesh;
    interpret mode here), every W4/W8 call — and the bf16 target's W8-draft
    spec rounds — lowers its projections to it, bf16 calls never do, and
    ``mpmm_kernel_calls`` counts exactly the calls whose span says so."""
    traces = []
    kernel = mpmm_mod.mpmm_pallas
    monkeypatch.setattr(layers, "mpmm_backend", lambda: "pallas")
    monkeypatch.setattr(
        mpmm_mod, "mpmm_pallas",
        lambda *a, **kw: traces.append(kw["w_bits"]) or kernel(*a, **kw),
    )
    jax.clear_caches()  # the shared step programs were traced on the XLA path
    try:
        eng = _engine(setup)
        reqs = _submit_mix(eng, setup[0].vocab)
        _, events = _traced(tmp_path, lambda: _drive(eng))
    finally:
        jax.clear_caches()  # ... and must not keep the kernel for later tests
    assert set(traces) == {4, 8}
    assert all(r.done and len(r.out_tokens) == 6 for r in reqs)
    paths = _call_paths(events)
    for w_bits, draft_bits, path in paths:
        quantized = w_bits < 16 or (draft_bits or 16) < 16
        assert path == ("pallas" if quantized else "none")
    kernel_calls = sum(p == "pallas" for _, _, p in paths)
    assert kernel_calls > 0 and eng.stats.mpmm_kernel_calls == kernel_calls
