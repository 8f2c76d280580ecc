"""ServeEngine: continuous batching with per-request precision and
prefix-cache-aware chunked prefill.

One engine step:

  1. **Admit** — waiting requests are admitted FCFS while batch slots and KV
     pages last (head-of-line blocking, see scheduler.py).  Admission looks
     the request's prompt up in the per-pool **prefix cache**
     (prefix_cache.py): the longest chain of cached full token blocks is
     adopted read-only into the request's page table (refcounted sharing),
     capped at ``prompt_len - 1`` so at least one token runs through the
     model to produce the first logits — when that cap lands mid-page, the
     shared page is **copy-on-write forked** before the suffix overwrites
     it.  Only the *uncached* suffix needs fresh pages and compute, so
     admission cost scales with uncached tokens.
  2. **Chunked prefill** (prefill.py) — prefilling requests advance through
     their uncached suffix at most ``prefill_chunk`` tokens per step,
     interleaved with running decodes (long prompts no longer stall the
     batch).  Requests whose remaining suffix fits one chunk are grouped by
     (w_bits, kv_bits, pow2 length bucket) and share ONE
     ``chunk_prefill_step`` call with ragged ``q_lens`` — mixed-length
     admissions no longer pay one trace+call per distinct prompt length.
     The call that completes a prompt yields the request's first token, and
     the request's full prompt blocks are registered back into the prefix
     cache for followers to hit.
  3. **Grow/evict** — any running request about to cross a page boundary
     gets one more page; if the pool is dry the prefix cache's LRU retained
     pages are evicted first, then the youngest running request on that pool
     is preempted.  Preemption *releases* pages into the cache (registering
     every materialized full block), so a preempted request usually resumes
     from still-cached pages and recomputes only what eviction actually
     took.
  4. **Decode** — running requests are grouped by (w_bits, kv_bits); each
     group makes ONE ``paged_decode_step`` call (batched mpmm projections +
     paged-kernel attention reading the page pool in place), which also
     scatters the new K/V token straight into its page — the engine just
     adopts the returned pools.  Batch and table-width dimensions are
     pow2-bucketed so admitting/retiring one request doesn't retrace.  A
     step that decodes ≥2 different precision groups is counted in
     ``stats.mixed_precision_steps``.  Requests with ``spec_k > 0`` instead
     run **speculative rounds** (serve/spec_decode.py): one fused jitted
     call drafts up to ``spec_k`` greedy tokens at the request's cheap
     ``draft_bits`` weight set and verifies the window at its target
     ``w_bits`` through the chunk-attention kernel; exact greedy acceptance
     emits 1..spec_k+1 tokens per round (bit-identical to plain decode),
     and rejected tail pages roll back to the pool via
     ``PagedKVCache.truncate``.

**Generation API** (serve/params.py, serve/outputs.py): ``submit(prompt,
SamplingParams, PrecisionParams)`` enqueues a request; ``generate()``
streams one ``StreamEvent`` per emitted token plus a terminal
``GenerationOutput`` per request.  Every hot path ends in the shared
position-keyed sampling op (``kernels/ops.py::sample_tokens``) inside the
same jitted graph as the model step: per-row temperature/top-k/top-p with
keys ``fold_in(PRNGKey(seed), position)``, so sampled streams are
reproducible under a fixed seed regardless of batch composition, bucketing
or preemption — and ``temperature == 0`` rows are exact argmax, bit-equal
to greedy decode.  Speculative rounds run speculative *rejection* sampling
(serve/spec_decode.py), which preserves the target distribution for sampled
requests and collapses to exact-equality acceptance for greedy ones.

A request finishes on its token budget (``finish_reason == "length"``) OR
the moment it emits its ``eos_id``/``stop_tokens`` (``"stop"`` — prefill,
plain decode, and mid-verify-window alike).  A request whose context
(prompt + max_new_tokens) could never fit its page pool is FAILED
(``"failed"``) at submit/admission with a clear error instead of being
allowed to preempt-readmit-livelock the engine.

Requests never wait for batch-mates: a request admitted at step N starts
prefilling at step N alongside requests decoding since long before.
Archs with frontend prefix embeddings (cfg.prefix_len > 0) keep the legacy
one-shot-prefill path and skip the prefix cache (prefix embeddings are not
token-addressable).

**Tracing.**  ``step()`` opens host spans (``jax.profiler.TraceAnnotation``)
at each layer boundary; they record only while the JAX profiler runs, on the
clock of the device trace.  Nesting and args:

  serve.step (step)
    serve.admit (admitted, waiting, rids)
    serve.prefill (groups)
      serve.chunk (w_bits, kv_bits, rows, chunk, width, rids)
        serve.inputs / serve.call (program, mpmm) / serve.outputs
    serve.page_room (preempted)
    serve.decode (groups)
      serve.decode_group (w_bits, kv_bits, rows, bsz, width, rids)
      serve.spec_group (w_bits, kv_bits, draft_bits, rows, bsz, width, rids)
        serve.inputs / serve.call (program, mpmm) / serve.outputs

``serve.inputs`` builds the host arrays of one group call, ``serve.call``
runs the jitted program until its result is ready, ``serve.outputs`` adopts
the pools and does the per-request bookkeeping.  A speculative group whose
windows all shrink to one token runs, and is traced, as a plain decode
group.  The legacy path opens
``serve.admit`` and ``serve.prefill`` with one ``serve.call`` per prefill
group.  The step programs are named ``serve_decode``, ``serve_chunk``,
``serve_spec`` and ``serve_prefill`` (``program``); ``mpmm`` says what the
program's quantized projections run on: ``"pallas"`` (the mpmm kernel),
``"xla"`` (the dequant reference) or ``"none"`` (bf16 weights only; see
``models.layers.mpmm_backend``).  ``EngineStats`` keeps,
traced or not, the seconds inside ``serve.step`` (``step_s``),
``serve.prefill`` (``prefill_s``), ``serve.decode`` (``decode_s``), and
inside ``serve.call`` of decode and spec groups (``decode_wait_s``) and of
chunks (``chunk_wait_s``), and counts the decode, spec and chunk calls that
ran the mpmm kernel (``mpmm_kernel_calls``); ``ServeRequest.admitted_ts`` is
the moment a request was first admitted.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
import warnings
from dataclasses import dataclass, field
from typing import Iterable, Iterator, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.kernels import ops
from repro.models import layers
from repro.models import transformer as model_lib
from repro.serve.decode import paged_decode_sample
from repro.serve.kv_cache import PagedKVCache
from repro.serve.outputs import (
    FINISH_FAILED,
    FINISH_LENGTH,
    FINISH_STOP,
    GenerationOutput,
    StreamEvent,
)
from repro.serve.params import (
    LEGACY_PRECISION_KWARGS,
    LEGACY_SAMPLING_KWARGS,
    PrecisionParams,
    SamplingParams,
)
from repro.serve.prefill import bucket_pow2, chunk_prefill_sample
from repro.serve.prefix_cache import PrefixCache, block_hashes
from repro.serve.request import RequestState, ServeRequest
from repro.serve.scheduler import Scheduler
from repro.serve.spec_decode import clip_stop, plan_windows, spec_decode_round

_SUPPORTED_FAMILIES = ("dense", "vlm", "audio", "moe")


def _make_jits(mesh):
    """Jitted engine steps closed over ``mesh`` (mesh objects aren't
    hashable jit statics, so it rides in the closure).  The four pool
    arguments of decode/chunk/spec are donated so their in-kernel K/V
    scatters run in place — keep ``donate_argnums`` in sync with the
    signatures here, the single place they are spelled.  ``samp`` is the
    per-row sampling-parameter tuple (temperature, top_k, top_p, seed,
    position) every hot path now ends in: the next-token draw happens inside
    the same jitted graph as the model step, never host-side.  The function
    names name the programs in a profiler trace."""

    def serve_prefill(p, b, cfg, max_len):
        return model_lib.prefill(p, b, cfg, max_len, mesh)

    def serve_decode(p, t, ln, tb, vl, samp, pk, pv, pks, pvs, cfg):
        return paged_decode_sample(
            p, t, ln, tb, vl, samp, pk, pv, pks, pvs, cfg=cfg, mesh=mesh
        )

    def serve_chunk(p, t, qs, ql, tb, samp, pk, pv, pks, pvs, cfg):
        return chunk_prefill_sample(
            p, t, qs, ql, tb, samp, pk, pv, pks, pvs, cfg=cfg, mesh=mesh
        )

    def serve_spec(dp, p, t, ln, tb, vl, nd, samp, pk, pv, pks, pvs, cfg, spec_k):
        return spec_decode_round(
            dp, p, t, ln, tb, vl, nd, samp, pk, pv, pks, pvs,
            cfg=cfg, spec_k=spec_k, mesh=mesh,
        )

    return (
        jax.jit(serve_prefill, static_argnames=("cfg", "max_len")),
        jax.jit(serve_decode, static_argnames=("cfg",), donate_argnums=(6, 7, 8, 9)),
        jax.jit(serve_chunk, static_argnames=("cfg",), donate_argnums=(6, 7, 8, 9)),
        jax.jit(
            serve_spec, static_argnames=("cfg", "spec_k"),
            donate_argnums=(8, 9, 10, 11),
        ),
    )


@functools.lru_cache(maxsize=1)
def _shared_jits():
    """The mesh=None jits, shared process-wide so a fresh engine reuses
    compiled code; meshed engines keep per-engine closures."""
    return _make_jits(None)


def _built(args: dict, on: bool) -> dict:
    """A span's args as recorded: none unless tracing, callables called."""
    if not on:
        return {}
    return {k: v() if callable(v) else v for k, v in args.items()}


@dataclass
class EngineStats:
    prefill_s: float = 0.0  # inside serve.prefill
    decode_s: float = 0.0  # inside serve.decode
    decode_steps: int = 0  # batched decode kernel-group calls
    engine_steps: int = 0
    tokens_out: int = 0
    prefills: int = 0  # completed request prefills
    prefill_chunks: int = 0  # chunk_prefill_step calls
    preemptions: int = 0
    mixed_precision_steps: int = 0  # engine steps decoding >= 2 precision groups
    occupancy_sum: int = 0  # sum of decode group sizes (mean = /decode_steps)
    spec_rounds: int = 0  # fused draft+verify group calls
    spec_draft_tokens: int = 0  # tokens drafted at draft_bits
    spec_accepted_tokens: int = 0  # drafts the target verify accepted
    failed: int = 0  # requests rejected at admission (context can't fit)
    group_calls: dict = field(default_factory=dict)  # (w_bits, kv_bits) -> calls
    prefix_hit_tokens: int = 0  # prompt tokens served from cached pages
    prefix_new_tokens: int = 0  # prompt tokens actually computed
    # host perf_counter seconds like prefill_s/decode_s (spans: module docstring)
    step_s: float = 0.0  # inside serve.step, the whole of step()
    decode_wait_s: float = 0.0  # serve.call of decode and spec groups
    chunk_wait_s: float = 0.0  # serve.call of chunk-prefill calls
    mpmm_kernel_calls: int = 0  # decode/spec/chunk calls with mpmm="pallas"

    @property
    def mean_batch_occupancy(self) -> float:
        return self.occupancy_sum / max(self.decode_steps, 1)

    @property
    def decode_tok_per_s(self) -> float:
        return self.tokens_out / max(self.decode_s, 1e-9)

    @property
    def spec_accept_rate(self) -> float:
        """Fraction of drafted tokens the target-precision verify accepted."""
        return self.spec_accepted_tokens / max(self.spec_draft_tokens, 1)

    @property
    def prefix_hit_rate(self) -> float:
        """Fraction of admitted prefill tokens served from cached pages
        instead of computed.  Readmissions count too: a preempted request's
        replayed chain (prompt + generated tokens) is prefill work, and
        blocks it re-adopts are recompute genuinely avoided."""
        total = self.prefix_hit_tokens + self.prefix_new_tokens
        return self.prefix_hit_tokens / max(total, 1)


class ServeEngine:
    @staticmethod
    def supports(cfg: ArchConfig) -> bool:
        """Continuous batching needs every layer's cache in one paged pool:
        attention families only, and no unstacked leading dense MoE blocks."""
        return cfg.family in _SUPPORTED_FAMILIES and not (
            cfg.family == "moe" and cfg.first_dense
        )

    def __init__(
        self,
        cfg: ArchConfig,
        params,
        *,
        max_slots: int = 8,
        num_pages: Optional[int] = None,
        page_size: int = 16,
        prefill_chunk: int = 32,
        enable_prefix_cache: bool = True,
        spec_k: int = 0,
        draft_bits: int = 4,
        mesh=None,
    ):
        if not self.supports(cfg):
            raise NotImplementedError(
                f"ServeEngine needs a uniform attention-cache stack "
                f"(families {_SUPPORTED_FAMILIES}, no leading dense MoE blocks); "
                f"{cfg.name} is {cfg.family!r}"
                + (" with first_dense" if cfg.first_dense else "")
                + " — use repro.train.server.Server, which falls back to wave batching"
            )
        if prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got {prefill_chunk}")
        if spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        if draft_bits not in (4, 8, 16):
            raise ValueError(f"draft_bits must be 4, 8 or 16, got {draft_bits}")
        self.spec_k = spec_k  # submit() default: 0 = plain greedy decode
        self.draft_bits = draft_bits  # submit() default draft precision
        self.cfg = cfg
        self.mesh = mesh
        self.page_size = page_size
        self.num_pages = num_pages if num_pages is not None else max_slots * 32
        self.prefill_chunk = prefill_chunk
        # frontend prefix embeddings are not token-addressable: those archs
        # keep the legacy one-shot grouped prefill and no prefix cache
        self._legacy_prefill = bool(cfg.prefix_len)
        self._prefix_enabled = enable_prefix_cache and not self._legacy_prefill
        self._sched = Scheduler(max_slots)
        self._params = {16: params}  # w_bits -> param tree (quantized lazily)
        self._caches: dict[int, PagedKVCache] = {}  # kv_bits -> page pool
        self._prefix: dict[int, PrefixCache] = {}  # kv_bits -> prefix cache
        self._block_hashes: dict[int, list[bytes]] = {}  # rid -> prompt chain
        self._next_arrival = 0
        self._next_rid = 0
        self.finished: list[ServeRequest] = []
        # Donating the pools lets XLA run the fused token-append scatter in
        # place (None scales in the kv16 case contribute no buffers); the
        # engine rebinds via cache.set_pools right after each call and never
        # reuses the old arrays, so the donated buffers are safely dead.
        (self._prefill_fn, self._decode_fn, self._chunk_fn,
         self._spec_fn) = _shared_jits() if mesh is None else _make_jits(mesh)
        self.stats = EngineStats()

    # -------------------------------------------------------------- plumbing
    @contextlib.contextmanager
    def _span(self, name: str, counter: Optional[str] = None, **args):
        """Host span ``name`` (module docstring), recorded only while the JAX
        profiler runs.  ``counter`` names an ``EngineStats`` field that gains
        the span's elapsed seconds, traced or not.  An arg given as a
        callable is built only while tracing; the yielded ``note(**args)``
        adds args known only once the work is done."""
        on = jax.profiler.TraceAnnotation.is_enabled()
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name, **_built(args, on)) as ann:
                yield (lambda **more: ann.set_metadata(**_built(more, on)))
        finally:
            if counter is not None:
                setattr(self.stats, counter,
                        getattr(self.stats, counter) + time.perf_counter() - t0)

    @staticmethod
    def _mpmm_path(*w_bits: int) -> str:
        """What the quantized projections of a program over these weight
        sets run on — ``"pallas"`` (the mpmm kernel) or ``"xla"``, as
        ``models.layers.mpmm_backend`` decides — or ``"none"`` when every set
        is plain bf16.  Static per program, so known at dispatch."""
        if all(b >= 16 for b in w_bits):
            return "none"
        return layers.mpmm_backend()

    def params_for(self, w_bits: int):
        if w_bits not in self._params:
            self._params[w_bits] = model_lib.quantize_params(self._params[16], w_bits)
        return self._params[w_bits]

    def cache_for(self, kv_bits: int) -> PagedKVCache:
        if kv_bits not in self._caches:
            self._caches[kv_bits] = PagedKVCache(
                self.cfg,
                num_pages=self.num_pages,
                page_size=self.page_size,
                kv_bits=kv_bits,
            )
            if self._prefix_enabled:
                self._prefix[kv_bits] = PrefixCache(self._caches[kv_bits])
        return self._caches[kv_bits]

    def prefix_cache_for(self, kv_bits: int) -> Optional[PrefixCache]:
        self.cache_for(kv_bits)
        return self._prefix.get(kv_bits)

    def _group_cfg(self, kv_bits: int) -> ArchConfig:
        return dataclasses.replace(self.cfg, serve_kv_bits=kv_bits)

    def _prefill_len(self, req: ServeRequest) -> int:
        return self.cfg.prefix_len + len(req.feed_tokens())

    def _max_ctx(self, req: ServeRequest) -> int:
        """Largest cache the request can ever need: every position its feed
        chain can reach.  The final emitted token is never fed back (the
        request finishes on emission), so the worst-case cache is one short
        of prompt + max_new_tokens — a request sized exactly to the pool
        must admit, not be rejected."""
        return self.cfg.prefix_len + len(req.prompt) + req.max_new_tokens - 1

    def _prefilling(self, req: ServeRequest) -> bool:
        return req.cache_len < self._prefill_len(req)

    def _chain_salt(self, req: ServeRequest) -> tuple:
        # K/V values depend on the weight precision that computed them: W4
        # and W8 requests must never share pages even in the same kv pool
        return ("w", req.w_bits)

    # ---------------------------------------------------------------- submit
    def _legacy_submit_params(
        self, max_new_tokens, sampling, precision, legacy
    ) -> tuple[SamplingParams, PrecisionParams]:
        """Deprecated-kwargs shim: ``submit(prompt, 16, w_bits=4, ...)``
        still works, warning once per call, by packing the flat kwargs into
        the structured types.  Mixing a structured param with flat kwargs
        that belong inside it is an error, not a silent merge."""
        warnings.warn(
            "ServeEngine.submit(prompt, max_new_tokens, **flat_kwargs) is "
            "deprecated; pass submit(prompt, SamplingParams(...), "
            "PrecisionParams(...)) instead",
            DeprecationWarning,
            stacklevel=3,
        )
        unknown = set(legacy) - LEGACY_SAMPLING_KWARGS - LEGACY_PRECISION_KWARGS
        if unknown:
            raise TypeError(f"submit() got unexpected kwargs {sorted(unknown)}")
        samp_kw = {k: v for k, v in legacy.items() if k in LEGACY_SAMPLING_KWARGS}
        prec_kw = {k: v for k, v in legacy.items() if k in LEGACY_PRECISION_KWARGS}
        if max_new_tokens is not None:
            samp_kw["max_new_tokens"] = int(max_new_tokens)
        if sampling is not None and samp_kw:
            raise TypeError(
                f"pass {sorted(samp_kw)} inside SamplingParams, not alongside it"
            )
        if precision is not None and prec_kw:
            raise TypeError(
                f"pass {sorted(prec_kw)} inside PrecisionParams, not alongside it"
            )
        sampling = sampling if sampling is not None else SamplingParams(**samp_kw)
        precision = (
            precision if precision is not None else PrecisionParams(**prec_kw)
        )
        return sampling, precision

    def submit(
        self,
        prompt: np.ndarray,
        sampling: Optional[Union[SamplingParams, int]] = None,
        precision: Optional[PrecisionParams] = None,
        *,
        rid: Optional[int] = None,
        **legacy,
    ) -> ServeRequest:
        """Enqueue one request: ``submit(prompt, SamplingParams(...),
        PrecisionParams(...))``.  Omitted params take the engine defaults
        (greedy, 16 tokens; the engine's configured precisions).  The old
        flat signature ``submit(prompt, max_new_tokens, w_bits=..., ...)``
        still works through a DeprecationWarning shim."""
        if isinstance(sampling, (int, np.integer)) or legacy:
            max_new = sampling if isinstance(sampling, (int, np.integer)) else None
            sampling = None if max_new is not None else sampling
            sampling, precision = self._legacy_submit_params(
                max_new, sampling, precision, legacy
            )
        sampling = SamplingParams() if sampling is None else sampling
        precision = PrecisionParams() if precision is None else precision
        w_bits = (
            self.cfg.serve_w_bits if precision.w_bits is None else precision.w_bits
        )
        kv_bits = (
            self.cfg.serve_kv_bits
            if precision.kv_bits is None
            else precision.kv_bits
        )
        spec_k = self.spec_k if precision.spec_k is None else precision.spec_k
        draft_bits = (
            self.draft_bits
            if precision.draft_bits is None
            else precision.draft_bits
        )
        if rid is not None:
            live = {
                r.rid for r in (*self._sched.waiting, *self._sched.running)
            }
            if rid in live:
                raise ValueError(f"rid {rid} is already in flight")
        req = ServeRequest(
            rid=self._next_rid if rid is None else rid,
            prompt=np.asarray(prompt, np.int32),
            max_new_tokens=sampling.max_new_tokens,
            w_bits=w_bits,
            kv_bits=kv_bits,
            eos_id=sampling.eos_id,
            stop_tokens=sampling.stop_tokens,
            spec_k=spec_k,
            draft_bits=draft_bits,
            temperature=sampling.temperature,
            top_k=sampling.top_k,
            top_p=sampling.top_p,
            seed=sampling.seed,
            arrival=self._next_arrival,
            submit_ts=time.perf_counter(),
        )
        self._next_rid = max(self._next_rid, req.rid + 1)
        self._next_arrival += 1
        cache = self.cache_for(kv_bits)
        if cache.pages_for(self._max_ctx(req)) > cache.num_pages:
            raise ValueError(
                f"request can never fit: prompt + max_new_tokens needs "
                f"{cache.pages_for(self._max_ctx(req))} pages; the kv{kv_bits} "
                f"pool only has {cache.num_pages}"
            )
        self._sched.submit(req)
        return req

    # ------------------------------------------------- admission (prefix-aware)
    def _fail(self, req: ServeRequest, msg: str) -> None:
        """Reject a request that can never run (e.g. its worst-case context
        exceeds the whole page pool): surface a clear error instead of the
        admit -> grow -> self-preempt -> readmit livelock, which ``run()``
        would count as progress forever."""
        if req in self._sched.waiting:
            self._sched.waiting.remove(req)
        req.state = RequestState.FAILED
        req.error = msg
        req.finish_reason = FINISH_FAILED
        self._block_hashes.pop(req.rid, None)
        self.stats.failed += 1
        self.finished.append(req)

    def _admissible(self, req: ServeRequest) -> bool:
        """Cap admissible context against pool capacity: ``submit`` already
        rejects oversized requests, but admission re-checks so a request
        enqueued behind the engine's back (or replayed against a smaller
        pool) fails loudly here instead of livelocking the decode loop."""
        cache = self.cache_for(req.kv_bits)
        need = cache.pages_for(self._max_ctx(req))
        if need <= cache.num_pages:
            return True
        self._fail(
            req,
            f"context can never fit: prompt + max_new_tokens needs {need} "
            f"pages; the kv{req.kv_bits} pool only has {cache.num_pages}",
        )
        return False

    def _try_admit(self, req: ServeRequest) -> bool:
        """Admission check with commitment: on True the request holds its
        full-prompt page table — cached prefix blocks adopted shared, the
        divergence page CoW-forked, fresh pages for the uncached suffix."""
        if not self._admissible(req):
            return False
        cache = self.cache_for(req.kv_bits)
        ps = cache.page_size
        plen = self._prefill_len(req)
        n_pages = cache.pages_for(plen)
        pc = self._prefix.get(req.kv_bits)
        hashes: list[bytes] = []
        pages: list[int] = []
        if pc is not None:
            # memoize the chain across admission retries: a head-of-line
            # request blocked on a full pool is re-checked every engine step,
            # and its feed chain only changes across preempt/readmit cycles
            feed = req.feed_tokens()
            hashes = self._block_hashes.get(req.rid, [])
            if len(hashes) != len(feed) // ps:
                hashes = block_hashes(feed, ps, self._chain_salt(req))
                self._block_hashes[req.rid] = hashes
            pages = pc.match(hashes)
        # at least one suffix token must run through the model to produce the
        # first-token logits, so a full-prompt hit is capped — the capped
        # block's page is then shared *and* about to be written: the
        # copy-on-write divergence fork below.  If the pool can't afford a
        # candidate (the fork needs one extra transient page, and adopted
        # pages can't be reclaimed for their own request), degrade the hit:
        # capped -> floored to a page multiple (no fork) -> cold.
        best = min(len(pages) * ps, plen - 1)
        candidates = [best]
        if best % ps:
            candidates.append(best - best % ps)
        if candidates[-1] != 0:
            candidates.append(0)
        for hit in candidates:
            shared = pages[: -(-hit // ps)] if hit else []
            fork_needed = 1 if hit % ps else 0
            fresh_needed = n_pages - len(shared) + fork_needed
            reclaimable = max(0, cache.num_reclaimable - len(shared))
            if cache.num_free + reclaimable < fresh_needed:
                continue
            try:
                cache.allocate(req.rid, n_pages, prefix_pages=tuple(shared))
            except MemoryError:
                continue
            if pc is not None:
                pc.acquire_note(shared)
                if fork_needed:
                    try:
                        cache.fork_page(req.rid, hit // ps)
                    except MemoryError:
                        cache.free(req.rid)
                        continue
                    pc.stats.forks += 1
            req.cache_len = hit
            if pc is not None:  # both ratio sides counted once, on adoption
                pc.stats.lookups += 1
                pc.stats.lookup_tokens += len(hashes) * ps
                pc.stats.hit_tokens += hit
            self.stats.prefix_hit_tokens += hit
            self.stats.prefix_new_tokens += plen - hit
            return True
        return False

    def _admit(self, fits) -> list[ServeRequest]:
        """FCFS admission through ``fits`` (span ``serve.admit``); stamps each
        request's first admission (a readmission keeps it)."""

        def admit(req: ServeRequest) -> bool:
            if not fits(req):
                return False
            if req.admitted_ts is None:
                req.admitted_ts = time.perf_counter()
            return True

        with self._span("serve.admit") as note:
            admitted = self._sched.admit(admit)
            note(admitted=len(admitted), waiting=len(self._sched.waiting),
                 rids=lambda: [r.rid for r in admitted])
        return admitted

    # ------------------------------------------------------- chunked prefill
    def _prefill_pump(self) -> None:
        """Advance every prefilling request by at most one chunk.  Requests
        finishing this step are grouped by (w_bits, kv_bits, pow2 bucket of
        their remaining suffix) into one ragged call each; longer prompts
        batch into one ``prefill_chunk``-wide ragged call per precision and
        keep the batch decoding between their chunks."""
        pumping = [
            r
            for r in self._sched.running
            if r.state is RequestState.RUNNING and self._prefilling(r)
        ]
        if not pumping:
            return
        pumping.sort(key=lambda r: r.arrival)
        with self._span("serve.prefill", "prefill_s") as note:
            groups: dict[tuple, list[ServeRequest]] = {}
            for req in pumping:
                rem = self._prefill_len(req) - req.cache_len
                if rem <= self.prefill_chunk:
                    # clamp to the chunk budget: for non-pow2 budgets the pow2
                    # bucket could otherwise exceed the per-step token bound
                    key = (req.w_bits, req.kv_bits,
                           min(bucket_pow2(rem), self.prefill_chunk))
                else:  # long runners batch too: one ragged call per precision
                    key = (req.w_bits, req.kv_bits, "long")
                groups.setdefault(key, []).append(req)
            note(groups=len(groups))
            for key, reqs in sorted(groups.items(), key=lambda kv: kv[1][0].arrival):
                chunk = self.prefill_chunk if key[2] == "long" else key[2]
                self._chunk_group(reqs, chunk)

    def _samp_arrays(self, reqs: list[ServeRequest], bsz: int):
        """Per-row (temperature, top_k, top_p, seed, position) arrays for a
        bucketed group call — or ``None`` when the whole group is greedy, so
        the jitted graph is the bare pre-sampling argmax (zero sampling
        compute; greedy is the default and the common case).  ``top_k`` /
        ``top_p`` entries are likewise ``None`` when no row in the group
        uses them: the vocab argsort the mask needs is elided statically
        (temperature-only sampling costs one gumbel field).  The elided and
        full graphs draw identical tokens for any given row, so grouping
        stays invisible to the stream.

        ``position`` is each request's next emission index
        (= len(out_tokens)) — the PRNG key coordinate that makes sampled
        streams batch-composition and preemption independent.  Padding rows
        stay temperature 0 (greedy argmax of garbage logits, sliced off by
        the caller)."""
        if all(r.greedy for r in reqs):
            return None
        temps = np.zeros(bsz, np.float32)
        top_ks = np.zeros(bsz, np.int32)
        top_ps = np.ones(bsz, np.float32)
        seeds = np.zeros(bsz, np.uint32)
        positions = np.zeros(bsz, np.int32)
        for i, r in enumerate(reqs):
            temps[i] = r.temperature
            top_ks[i] = r.top_k
            top_ps[i] = r.top_p
            seeds[i] = r.seed
            positions[i] = len(r.out_tokens)
        # numpy, not device arrays: the jitted call transfers them with its
        # other host operands instead of five eager device_puts per step
        return (
            temps,
            top_ks if any(r.top_k > 0 for r in reqs) else None,
            top_ps if any(r.top_p < 1.0 for r in reqs) else None,
            seeds,
            positions,
        )

    def _chunk_group(self, reqs: list[ServeRequest], chunk: int) -> None:
        w_bits, kv_bits = reqs[0].w_bits, reqs[0].kv_bits
        cache = self.cache_for(kv_bits)
        cfg_g = self._group_cfg(kv_bits)
        rids = [r.rid for r in reqs]
        n = len(reqs)
        with self._span("serve.chunk", w_bits=w_bits, kv_bits=kv_bits, rows=n,
                        chunk=chunk, rids=rids) as note:
            with self._span("serve.inputs"):
                # pow2-bucket the batch dimension like decode does: padding
                # rows have q_len 0, so they scatter nothing and their tokens
                # are sliced off
                bsz = bucket_pow2(n)
                tokens = np.zeros((bsz, chunk), np.int32)
                q_start = np.zeros(bsz, np.int32)
                q_lens = np.zeros(bsz, np.int32)
                for i, r in enumerate(reqs):
                    feed = r.feed_tokens()
                    q_start[i] = r.cache_len
                    q_lens[i] = min(len(feed) - r.cache_len, chunk)
                    tokens[i, : q_lens[i]] = feed[r.cache_len : r.cache_len + q_lens[i]]
                width = max(len(cache.table(r)) for r in rids)
                width = bucket_pow2(width)  # pow2-bucket to limit retraces
                tables = np.zeros((bsz, width), np.int32)
                tables[:n] = cache.table_array(rids, width)
                inputs = (
                    self.params_for(w_bits), jnp.asarray(tokens),
                    jnp.asarray(q_start), jnp.asarray(q_lens),
                    jnp.asarray(tables), self._samp_arrays(reqs, bsz),
                )
            note(width=width)
            mpmm = self._mpmm_path(w_bits)
            with self._span("serve.call", "chunk_wait_s", program="serve_chunk",
                            mpmm=mpmm):
                first_tok, new_pools = self._chunk_fn(
                    *inputs, cache.k, cache.v, cache.k_scale, cache.v_scale,
                    cfg=cfg_g,
                )
                jax.block_until_ready(first_tok)
            with self._span("serve.outputs"):
                cache.set_pools(*new_pools)  # chunk K/V scattered in-kernel
                self.stats.prefill_chunks += 1
                self.stats.mpmm_kernel_calls += mpmm == "pallas"
                first = np.asarray(first_tok)
                for i, req in enumerate(reqs):
                    req.cache_len += int(q_lens[i])
                    if not self._prefilling(req):
                        self._on_prefill_done(req, int(first[i]))

    def _on_prefill_done(self, req: ServeRequest, first_token: int) -> None:
        self.stats.prefills += 1
        if not req.out_tokens:  # fresh request: prefill yields token #1
            req.out_tokens.append(first_token)
            self.stats.tokens_out += 1
            req.ttft = time.perf_counter() - req.submit_ts
        # register the prompt's full blocks so followers (and this request's
        # own readmission) hit them
        self._register_blocks(req)
        if len(req.out_tokens) >= req.max_new_tokens or req.is_stop(
            req.out_tokens[-1]
        ):
            self._finish(req)

    def _register_blocks(self, req: ServeRequest) -> None:
        pc = self._prefix.get(req.kv_bits)
        if pc is None or req.cache_len < pc.block:
            return
        cache = self.cache_for(req.kv_bits)
        feed = req.feed_tokens()[: req.cache_len]
        hashes = self._block_hashes.get(req.rid, [])
        n_known = len(hashes)
        n_blocks = len(feed) // pc.block
        if n_blocks > n_known:  # decode extended the chain past the prompt
            hashes = block_hashes(feed, pc.block, self._chain_salt(req))
            self._block_hashes[req.rid] = hashes
        pc.register(hashes[:n_blocks], cache.table(req.rid)[:n_blocks])

    # --------------------------------------------- legacy prefill (prefix_len)
    def _admit_and_prefill(self) -> list[ServeRequest]:
        reserved: dict[int, int] = {}  # kv_bits -> pages spoken for this round

        def fits(req: ServeRequest) -> bool:
            if not self._admissible(req):
                return False
            cache = self.cache_for(req.kv_bits)
            need = cache.pages_for(self._prefill_len(req))
            if cache.num_free - reserved.get(req.kv_bits, 0) < need:
                return False
            reserved[req.kv_bits] = reserved.get(req.kv_bits, 0) + need
            return True

        admitted = self._admit(fits)
        if not admitted:
            return []
        groups: dict[tuple, list[ServeRequest]] = {}
        for req in admitted:
            key = (req.w_bits, req.kv_bits, self._prefill_len(req))
            groups.setdefault(key, []).append(req)
        with self._span("serve.prefill", "prefill_s", groups=len(groups)):
            for (w_bits, kv_bits, plen), reqs in groups.items():
                self._prefill_group(reqs, w_bits, kv_bits, plen)
        return admitted

    def _prefill_group(self, reqs, w_bits: int, kv_bits: int, plen: int) -> None:
        cfg_g = self._group_cfg(kv_bits)
        cache = self.cache_for(kv_bits)
        max_len = cache.pages_for(plen) * self.page_size
        tokens = jnp.asarray(np.stack([r.feed_tokens() for r in reqs]))
        batch = {"tokens": tokens}
        if self.cfg.prefix_len:
            from repro.models.frontends import prefix_embeddings

            batch["prefix_emb"] = prefix_embeddings(self.cfg, len(reqs))
        with self._span("serve.call", program="serve_prefill",
                        mpmm=self._mpmm_path(w_bits)):
            logits, kv = self._prefill_fn(
                self.params_for(w_bits), batch, cfg_g, max_len
            )
            jax.block_until_ready(logits)
        # legacy one-shot prefill samples on the returned logits (still a
        # jitted op — ops.sample_tokens — just not fused into the prefill)
        samp = self._samp_arrays(reqs, len(reqs))
        if samp is None:
            first = np.asarray(jnp.argmax(logits, axis=-1))
        else:
            temps, top_ks, top_ps, seeds, positions = samp
            first = np.asarray(
                ops.sample_tokens(
                    logits, ops.sample_keys(seeds, positions),
                    temps, top_ks, top_ps,
                )
            )
        for i, req in enumerate(reqs):
            cache.allocate(req.rid, cache.pages_for(plen))
            if cache.quantized:
                cache.write_prompt(
                    req.rid, kv["k"][:, i], kv["v"][:, i],
                    kv["k_scale"][:, i, ..., 0], kv["v_scale"][:, i, ..., 0],
                )
            else:
                cache.write_prompt(req.rid, kv["k"][:, i], kv["v"][:, i])
            req.cache_len = plen
            self._on_prefill_done(req, int(first[i]))

    # ---------------------------------------------------------------- decode
    def _step_need(self, req: ServeRequest) -> int:
        """Cache positions this step may write for ``req``: the speculative
        window (drafts + the verify's bonus slot) for spec requests, one
        token otherwise."""
        if req.spec_k and req.out_tokens and not self._prefilling(req):
            remaining = req.max_new_tokens - len(req.out_tokens)
            return min(req.spec_k, max(remaining - 1, 0)) + 1
        return 1

    def _ensure_page_room(self) -> None:
        """Grow page tables for requests crossing a page boundary; preempt
        youngest-first when a pool is dry (oldest requests get pages first).
        The allocation path evicts LRU prefix-cache pages before preempting.
        Speculative requests ask for their whole verify window up front but
        *degrade to a plain-decode window* under pressure rather than evict
        anyone — speculation must never cost a batch-mate its pages."""
        before = self.stats.preemptions
        with self._span("serve.page_room") as note:
            for req in sorted(self._sched.running, key=lambda r: r.arrival):
                if req.state is not RequestState.RUNNING:
                    continue
                cache = self.cache_for(req.kv_bits)
                need = self._step_need(req)
                while req.cache_len + need > cache.capacity_tokens(req.rid):
                    if cache.can_allocate(1):
                        cache.extend(req.rid, 1)
                        continue
                    if need > 1:
                        need = 1  # shrink the speculative window, keep decoding
                        continue
                    victim = self._sched.pick_victim(kv_bits=req.kv_bits)
                    self._preempt(victim)
                    if victim is req:
                        break
            note(preempted=self.stats.preemptions - before)

    def _release_pages(self, req: ServeRequest) -> None:
        """Register materialized full blocks into the prefix cache, then drop
        the request's references (retained pages keep serving hits until the
        pool reclaims them)."""
        self._register_blocks(req)
        self.cache_for(req.kv_bits).free(req.rid)
        self._block_hashes.pop(req.rid, None)

    def _preempt(self, req: ServeRequest) -> None:
        self._release_pages(req)
        self._sched.preempt(req)
        self.stats.preemptions += 1

    def _finish(self, req: ServeRequest) -> None:
        # a stop token is always the stream's last token (decode finishes on
        # emission, spec windows are clipped right after it), so the reason
        # is readable off the tail; "stop" wins when the budget's final
        # token happens to be a stop token
        req.finish_reason = (
            FINISH_STOP
            if req.out_tokens and req.is_stop(req.out_tokens[-1])
            else FINISH_LENGTH
        )
        self._release_pages(req)
        self._sched.finish(req)
        self.finished.append(req)

    def _batch_arrays(self, cache: PagedKVCache, reqs: list[ServeRequest]):
        """pow2-bucketed (tokens, lengths, tables, valid) for a decode or
        spec group — padding rows are masked so they never touch the pool."""
        rids = [r.rid for r in reqs]
        width = max(len(cache.table(r)) for r in rids)
        width = bucket_pow2(width)  # pow2-bucket to limit retraces
        n_real = len(reqs)
        bsz = bucket_pow2(n_real)
        tables = np.zeros((bsz, width), np.int32)
        tables[:n_real] = cache.table_array(rids, width)
        tokens = np.zeros((bsz, 1), np.int32)
        tokens[:n_real] = np.array([[r.out_tokens[-1]] for r in reqs], np.int32)
        lengths = np.zeros(bsz, np.int32)
        lengths[:n_real] = np.array([r.cache_len for r in reqs], np.int32)
        valid = np.arange(bsz) < n_real
        return tokens, lengths, tables, valid

    def _decode_groups(self) -> int:
        """One batched call per precision group: ``(w_bits, kv_bits)`` plain
        decode groups emit one token per request;
        ``(w_bits, draft_bits, kv_bits)`` speculative groups run one fused
        draft+verify round each (serve/spec_decode.py) and emit 1..spec_k+1
        tokens per request."""
        plain: dict[tuple[int, int], list[ServeRequest]] = {}
        spec: dict[tuple[int, int, int], list[ServeRequest]] = {}
        for req in self._sched.running:
            if (
                req.state is RequestState.RUNNING
                and req.out_tokens
                and not self._prefilling(req)
            ):
                if req.spec_k > 0:
                    spec.setdefault(req.spec_group_key, []).append(req)
                else:
                    plain.setdefault(req.group_key, []).append(req)
        n_groups = len(plain) + len(spec)
        if not n_groups:
            return 0
        with self._span("serve.decode", "decode_s", groups=n_groups):
            for (w_bits, kv_bits), reqs in sorted(plain.items()):
                self._plain_decode_group(reqs, w_bits, kv_bits)
            for (w_bits, draft_bits, kv_bits), reqs in sorted(spec.items()):
                self._spec_decode_group(reqs, w_bits, draft_bits, kv_bits)
        if n_groups >= 2:
            self.stats.mixed_precision_steps += 1
        return n_groups

    def _plain_decode_group(
        self, reqs: list[ServeRequest], w_bits: int, kv_bits: int
    ) -> None:
        reqs.sort(key=lambda r: r.arrival)
        cache = self.cache_for(kv_bits)
        cfg_g = self._group_cfg(kv_bits)
        n_real = len(reqs)
        with self._span("serve.decode_group", w_bits=w_bits, kv_bits=kv_bits,
                        rows=n_real, rids=lambda: [r.rid for r in reqs]) as note:
            with self._span("serve.inputs"):
                tokens, lengths, tables, valid = self._batch_arrays(cache, reqs)
                inputs = (
                    self.params_for(w_bits), jnp.asarray(tokens),
                    jnp.asarray(lengths), jnp.asarray(tables), jnp.asarray(valid),
                    self._samp_arrays(reqs, len(valid)),
                )
            note(bsz=len(valid), width=tables.shape[1])
            mpmm = self._mpmm_path(w_bits)
            with self._span("serve.call", "decode_wait_s", program="serve_decode",
                            mpmm=mpmm):
                sampled, new_pools = self._decode_fn(
                    *inputs, cache.k, cache.v, cache.k_scale, cache.v_scale,
                    cfg=cfg_g,
                )
                jax.block_until_ready(sampled)
            with self._span("serve.outputs"):
                cache.set_pools(*new_pools)  # new tokens scattered in-kernel
                next_tok = np.asarray(sampled[:n_real])
                for i, req in enumerate(reqs):
                    req.cache_len += 1
                    tok = int(next_tok[i])
                    req.out_tokens.append(tok)
                    self.stats.tokens_out += 1
                    if req.is_stop(tok) or len(req.out_tokens) >= req.max_new_tokens:
                        self._finish(req)
        self.stats.decode_steps += 1
        self.stats.mpmm_kernel_calls += mpmm == "pallas"
        self.stats.occupancy_sum += len(reqs)
        key = (w_bits, kv_bits)
        self.stats.group_calls[key] = self.stats.group_calls.get(key, 0) + 1

    def _spec_decode_group(
        self, reqs: list[ServeRequest], w_bits: int, draft_bits: int,
        kv_bits: int,
    ) -> None:
        """One fused speculative round for a same-precision group: draft
        ``spec_k`` tokens at ``draft_bits``, verify the window at ``w_bits``
        under rejection sampling (exact equality for greedy rows), emit the
        accepted prefix + the resample/bonus token, then roll rejected tail
        pages back to the pool."""
        reqs.sort(key=lambda r: r.arrival)
        cache = self.cache_for(kv_bits)
        cfg_g = self._group_cfg(kv_bits)
        spec_k = max(r.spec_k for r in reqs)
        capacities = np.array(
            [cache.capacity_tokens(r.rid) for r in reqs], np.int64
        )
        n_draft = plan_windows(reqs, capacities, spec_k)
        if not n_draft.any():
            # every row's window degenerated to one token (final-token
            # budget or page pressure): a plain decode call does the same
            # job without spec_k masked-out draft passes + a verify chunk
            self._plain_decode_group(reqs, w_bits, kv_bits)
            return
        n_real = len(reqs)
        with self._span("serve.spec_group", w_bits=w_bits, kv_bits=kv_bits,
                        draft_bits=draft_bits, rows=n_real,
                        rids=lambda: [r.rid for r in reqs]) as note:
            with self._span("serve.inputs"):
                tokens, lengths, tables, valid = self._batch_arrays(cache, reqs)
                nd = np.zeros(len(valid), np.int32)
                nd[:n_real] = n_draft
                inputs = (
                    self.params_for(draft_bits), self.params_for(w_bits),
                    jnp.asarray(tokens), jnp.asarray(lengths),
                    jnp.asarray(tables), jnp.asarray(valid), jnp.asarray(nd),
                    self._samp_arrays(reqs, len(valid)),
                )
            note(bsz=len(valid), width=tables.shape[1])
            mpmm = self._mpmm_path(w_bits, draft_bits)
            with self._span("serve.call", "decode_wait_s", program="serve_spec",
                            mpmm=mpmm):
                emit, accept, new_pools = self._spec_fn(
                    *inputs, cache.k, cache.v, cache.k_scale, cache.v_scale,
                    cfg=cfg_g, spec_k=spec_k,
                )
                jax.block_until_ready(emit)
            with self._span("serve.outputs"):
                cache.set_pools(*new_pools)  # draft K/V overwritten by verify K/V
                emit_np = np.asarray(emit)
                accept_np = np.asarray(accept)
                for i, req in enumerate(reqs):
                    n_acc = int(accept_np[i])
                    emitted = [int(t) for t in emit_np[i, : n_acc + 1]]
                    emitted, stopped = clip_stop(req, emitted)
                    req.out_tokens.extend(emitted)
                    req.cache_len += len(emitted)
                    self.stats.tokens_out += len(emitted)
                    self.stats.spec_draft_tokens += int(n_draft[i])
                    req.spec_drafted += int(n_draft[i])
                    # count only accepted drafts the request actually used: a
                    # mid-window stop token discards the accepted tail, and an
                    # accept rate the emission didn't cash in would overstate
                    # the CI-gated metric on eos-heavy workloads
                    used_acc = min(len(emitted) - 1, n_acc)
                    self.stats.spec_accepted_tokens += used_acc
                    req.spec_accepted += used_acc
                    # rollback: drop pages holding only rejected-window positions
                    self._truncate_tail(req)
                    if stopped or len(req.out_tokens) >= req.max_new_tokens:
                        self._finish(req)
        self.stats.spec_rounds += 1
        self.stats.decode_steps += 1
        self.stats.mpmm_kernel_calls += mpmm == "pallas"
        self.stats.occupancy_sum += len(reqs)
        key = (w_bits, kv_bits)
        self.stats.group_calls[key] = self.stats.group_calls.get(key, 0) + 1

    def _truncate_tail(self, req: ServeRequest) -> None:
        """Return table pages past ``req.cache_len`` to the pool.  Any
        prefix-cache entry for a dropped page is forgotten first: the verify
        window may have overwritten the page with rejected-token K/V, so it
        must not keep serving hits (registered blocks always precede the
        round's window, so in practice only defensively)."""
        cache = self.cache_for(req.kv_bits)
        keep = cache.pages_for(req.cache_len)
        tail = cache.table(req.rid)[keep:]
        if not tail:
            return
        pc = self._prefix.get(req.kv_bits)
        if pc is not None:
            pc.forget_pages(tail)
        cache.truncate(req.rid, req.cache_len)

    def step(self) -> bool:
        """One engine iteration; returns True if any work was done (failing
        an inadmissible request counts — it empties the queue)."""
        failed_before = self.stats.failed
        with self._span("serve.step", "step_s", step=self.stats.engine_steps):
            if self._legacy_prefill:
                admitted = self._admit_and_prefill()
                worked = bool(admitted)
            else:
                admitted = self._admit(self._try_admit)
                pumping = any(
                    r.state is RequestState.RUNNING and self._prefilling(r)
                    for r in self._sched.running
                )
                self._prefill_pump()
                worked = bool(admitted) or pumping
            self._ensure_page_room()
            n_groups = self._decode_groups()
            self.stats.engine_steps += 1
        return worked or n_groups > 0 or self.stats.failed > failed_before

    def run(self) -> list[ServeRequest]:
        """Drive until every submitted request finishes or fails; returns
        them (completion order — check ``req.failed``/``req.error`` for
        requests rejected at admission)."""
        while self._sched.has_work():
            if not self.step():
                raise RuntimeError(
                    "engine stalled: no request can be admitted (free pages: "
                    f"{ {b: c.num_allocatable for b, c in self._caches.items()} })"
                )
        return self.finished

    def generate(
        self,
        requests: Optional[Iterable] = None,
    ) -> Iterator[Union[StreamEvent, GenerationOutput]]:
        """Streaming generation: drive the engine and yield one
        ``StreamEvent`` per emitted token, then the terminal
        ``GenerationOutput`` of each request as it finishes — callers no
        longer hand-roll the ``step()`` loop.

        ``requests`` may mix already-submitted ``ServeRequest`` handles with
        ``(prompt, sampling[, precision])`` tuples or bare prompts, which
        are submitted here; ``None`` streams everything currently enqueued.
        Tokens are yielded in emission order the moment the engine step that
        produced them completes, so a consumer streams one request's tokens
        while its batch-mates are still decoding.  Events are append-only
        across preemptions (recompute replays cache state, never emissions).
        """
        if requests is None:
            track = [*self._sched.waiting, *self._sched.running]
        else:
            track = []
            for r in requests:
                if isinstance(r, ServeRequest):
                    track.append(r)
                elif isinstance(r, (tuple, list)):
                    track.append(self.submit(*r))
                else:
                    track.append(self.submit(r))
        streamed = {r.rid: 0 for r in track}
        pending = {r.rid for r in track}

        def drain(req: ServeRequest):
            terminal = req.done or req.failed
            while streamed[req.rid] < len(req.out_tokens):
                i = streamed[req.rid]
                streamed[req.rid] = i + 1
                last = terminal and streamed[req.rid] == len(req.out_tokens)
                yield StreamEvent(
                    rid=req.rid,
                    token=req.out_tokens[i],
                    index=i,
                    finish_reason=req.finish_reason if last else None,
                )
            if terminal:
                pending.discard(req.rid)
                yield GenerationOutput.from_request(req)

        # anything already emitted before generate() was called (e.g. a
        # handle from a partially-driven engine) streams out first
        for req in track:
            yield from drain(req)
        while pending:
            if not self.step():
                raise RuntimeError(
                    "engine stalled: no request can be admitted (free pages: "
                    f"{ {b: c.num_allocatable for b, c in self._caches.items()} })"
                )
            for req in track:
                if req.rid in pending:
                    yield from drain(req)
