"""Self-speculative decoding: cheap-precision draft, target-precision verify.

SPEED's premise is that one precision-scalable datapath trades bits for
throughput on the *same* weights (paper Sec. II-B).  The serving engine
already exploits that per-request (each request picks its ``w_bits``); this
module exploits it **per token**: the cheap low-bit weight set drafts ahead,
the request's own target precision verifies, and acceptance turns the
multi-precision machinery from a quality knob into a latency multiplier.

One speculative round for a batch of same-``(w_bits, draft_bits, kv_bits)``
requests is ONE jitted call (:func:`spec_decode_round`):

  1. **Draft** — ``spec_k`` single-token steps at ``draft_bits``
     (``serve/decode.py::paged_decode_step`` against the request's own paged
     KV cache), chained on-device: each step's chosen token feeds the next,
     so a round costs one host dispatch + one sync instead of ``spec_k + 1``.
     Draft tokens are drawn from the draft model's *sampling distribution*
     (``kernels/ops.py::sampling_probs`` — temperature/top-k/top-p masked;
     a one-hot, i.e. plain argmax, for greedy rows) and the per-step
     distributions are kept for the accept test.  Draft K/V is scattered
     into the request's pages as it goes (draft step ``i+1`` must attend to
     draft tokens ``1..i``).
  2. **Verify** — the window ``[last_token, d_1, .., d_k]`` runs ONE
     multi-token pass at the request's target ``w_bits`` through the chunked
     -prefill kernel (``ops.paged_mqa_verify`` — a verify window *is* a
     causal self-chunk), producing target logits (and target sampling
     distributions) at every window position.  The verify's target-precision
     K/V overwrites the draft K/V in the pages, so verify logits never
     depend on draft state.
  3. **Accept** — fused speculative *rejection sampling*
     (:func:`rejection_sample`): draft ``d_i`` is accepted with probability
     ``min(1, p_tgt(d_i) / p_draft(d_i))``; on the first reject the token at
     that position is resampled from the normalized residual
     ``max(p_tgt - p_draft, 0)``, and when every draft survives the verify's
     own next-token prediction rides along free (the "bonus" slot).  The
     emitted stream is therefore distributed EXACTLY as plain sampled decode
     (Leviathan et al.'s guarantee), and for greedy rows every distribution
     is a one-hot, collapsing the whole procedure to exact token equality —
     spec-on greedy output stays bit-identical to spec-off, which keeps the
     recompute-preemption safety invariant (serve/request.py) intact.

Every stochastic draw is position-keyed (``ops.sample_keys``): position
``p`` folds ``(seed, p, salt)`` with distinct salts for the draft sample,
the accept uniform, the residual resample and the bonus emission, so a round
is reproducible under a fixed seed whatever the batch looks like.  Round
*boundaries* (how many tokens each round commits) do depend on acceptance,
so a sampled spec stream matches plain sampled decode in distribution, not
bit-for-bit; greedy streams match exactly.

The host engine then advances ``cache_len`` by the emitted count and rolls
back rejected tail positions via ``PagedKVCache.truncate`` (dropping
now-empty tail pages back to the pool, after un-registering any prefix-cache
block whose page content the rejected window overwrote).  Positions between
the new ``cache_len`` and the end of the verify window hold K/V of rejected
tokens, but ``cache_len`` masking means they are never attended and the next
round overwrites them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.kernels import ops
from repro.models.layers import dense
from repro.serve.decode import paged_decode_step
from repro.serve.prefill import chunk_forward

# Salt constants for the independent draws one emission position needs.
# SALT_EMIT doubles as the plain-decode/prefill emission salt (sample_keys'
# default 0), so a spec round whose window degenerates to the bonus slot
# draws the same stream a plain decode step would.
SALT_EMIT = 0
SALT_DRAFT = 1
SALT_ACCEPT = 2
SALT_RESAMPLE = 3


def rejection_sample(
    drafts: jnp.ndarray,  # [B, K] int32 — draft tokens per window slot
    q_draft: jnp.ndarray,  # [B, K, V] draft sampling distributions
    q_target: jnp.ndarray,  # [B, K+1, V] target sampling distributions
    seeds: jnp.ndarray,  # [B] per-request PRNG seeds
    pos0: jnp.ndarray,  # [B] stream position of each row's window slot 0
    n_draft: jnp.ndarray,  # [B] int32 — live draft slots per row (<= K)
):
    """Fused speculative rejection sampling for one verify window.

    Returns ``(tokens [B, K+1], accept [B])``: row ``b`` emits
    ``tokens[b, : accept[b] + 1]`` — its accepted draft prefix plus either
    the residual resample at the first rejected slot or, when all
    ``n_draft[b]`` drafts survive, the bonus token drawn from the target's
    next-token distribution.  Greedy rows (one-hot distributions) reduce to
    exact token equality: accepted drafts ARE the target argmaxes, and the
    resample/bonus is the target argmax at the cut slot.
    """
    b, k = drafts.shape
    pos = pos0[:, None] + jnp.arange(k, dtype=jnp.int32)[None, :]  # [B, K]

    def flat_keys(salt):  # per-(row, slot) keys at the given salt
        return ops.sample_keys(
            jnp.repeat(seeds, k), pos.reshape(-1), salt
        )

    # accept test: u_i < p_tgt(d_i) / p_draft(d_i), first reject cuts
    p_t = jnp.take_along_axis(q_target[:, :k], drafts[..., None], -1)[..., 0]
    p_d = jnp.take_along_axis(q_draft, drafts[..., None], -1)[..., 0]
    if k:
        u = jax.vmap(lambda key: jax.random.uniform(key, ()))(
            flat_keys(SALT_ACCEPT)
        ).reshape(b, k)
    else:
        u = jnp.zeros((b, 0), jnp.float32)
    in_window = jnp.arange(k, dtype=jnp.int32)[None, :] < n_draft[:, None]
    ok = (u < p_t / jnp.maximum(p_d, 1e-20)) & in_window
    accept = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)

    # residual resample at every slot (only the first rejected one is used;
    # distinct position-keyed draws, so computing all K is just vectorized)
    if k:
        resid = jnp.maximum(q_target[:, :k] - q_draft, 0.0)
        rs = resid.sum(-1, keepdims=True)
        # degenerate residual (q_target == q_draft exactly) can only pair
        # with accept-prob 1, but guard the normalize anyway
        resid = jnp.where(rs > 0, resid / jnp.maximum(rs, 1e-20), q_target[:, :k])
        res_tok = ops.sample_from_probs(
            resid.reshape(b * k, -1), flat_keys(SALT_RESAMPLE)
        ).reshape(b, k)
    else:
        res_tok = jnp.zeros((b, 0), jnp.int32)

    # bonus token: all drafts survived -> draw the target's own next token.
    # Emitted at stream position pos0 + n_draft with the plain-emission salt,
    # exactly like a plain decode step at that position would.
    q_bonus = jnp.take_along_axis(
        q_target, n_draft[:, None, None], axis=1
    )[:, 0]
    bonus = ops.sample_from_probs(
        q_bonus, ops.sample_keys(seeds, pos0 + n_draft, SALT_EMIT)
    )

    full = accept >= n_draft
    if k:
        cut = jnp.take_along_axis(
            res_tok, jnp.clip(accept, 0, k - 1)[:, None], axis=1
        )[:, 0]
    else:
        cut = bonus
    final = jnp.where(full, bonus, cut)

    slots = jnp.arange(k + 1, dtype=jnp.int32)[None, :]
    drafts_pad = jnp.pad(drafts, ((0, 0), (0, 1)))
    tokens = jnp.where(
        slots < accept[:, None],
        drafts_pad,
        jnp.where(slots == accept[:, None], final[:, None], 0),
    ).astype(jnp.int32)
    return tokens, accept


def spec_decode_round(
    draft_params,  # param tree quantized at draft_bits
    params,  # param tree at the group's target w_bits
    tokens: jnp.ndarray,  # [B, 1] int32 — last emitted token per request
    lengths: jnp.ndarray,  # [B] int32 — tokens already in the cache
    tables: jnp.ndarray,  # [B, W] int32 page tables (zero-padded)
    valid: jnp.ndarray,  # [B] bool — False for pow2-bucket padding rows
    n_draft: jnp.ndarray,  # [B] int32 — draft tokens this row runs (<= spec_k)
    samp,  # (temperature [B], top_k [B], top_p [B], seed [B], position [B])
    pool_k: jnp.ndarray,  # [L, P, Hkv, ps, Dk]
    pool_v: jnp.ndarray,
    pool_ks,  # [L, P, Hkv, ps] f32 or None (kv_bits == 16)
    pool_vs,
    *,
    cfg: ArchConfig,
    spec_k: int,  # static: draft steps unrolled in the jitted graph
    mesh=None,
):
    """One fused draft+verify+accept round.

    Returns ``(emit_tokens [B, spec_k+1], accept [B], new_pools)``: row b
    emits ``emit_tokens[b, : accept[b] + 1]`` (``accept[b] <= n_draft[b]``,
    so a row never emits past its clipped window).  ``samp is None`` means
    the whole group is greedy: the graph is the pre-sampling exact-equality
    round (argmax drafts, token-match accept, zero sampling compute) — the
    general rejection-sampling path reduces to the same tokens through
    one-hot distributions, but an all-greedy group shouldn't pay vocab-sized
    probability algebra per draft step.  Every row's table must cover
    positions ``[0, lengths[b] + n_draft[b] + 1)`` — the engine guarantees
    this via ``_ensure_page_room`` (which degrades ``n_draft`` before
    evicting anyone).  Not jit'd here: the engine jits a closure over its
    mesh, mirroring decode/prefill.
    """
    pools = (pool_k, pool_v, pool_ks, pool_vs)
    b = tokens.shape[0]
    greedy = samp is None
    if not greedy:
        temps, top_ks, top_ps, seeds, pos0 = samp
    tok = tokens
    drafts = []
    draft_probs = []
    # --- draft: spec_k sampled steps at draft_bits, chained on-device.  Rows
    # past their own n_draft keep computing (the graph is static) but stop
    # appending K/V (valid=False drops the scatter) and their surplus drafts
    # can't be accepted (rejection_sample caps at n_draft).
    for i in range(spec_k):
        step_valid = valid & (i < n_draft)
        logits, pools = paged_decode_step(
            draft_params, tok, lengths + i, tables, step_valid, *pools,
            cfg=cfg, mesh=mesh,
        )
        if greedy:
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        else:
            qd = ops.sampling_probs(logits, temps, top_ks, top_ps)
            tok = ops.sample_from_probs(
                qd, ops.sample_keys(seeds, pos0 + i, SALT_DRAFT)
            )[:, None]
            draft_probs.append(qd)
        drafts.append(tok)
    wtok = jnp.concatenate([tokens, *drafts], axis=1)  # [B, spec_k + 1]

    # --- verify: one causal self-chunk at the target precision.  ctx_lens =
    # round-start lengths, so verify attends only to committed cache + the
    # window itself — never to draft K/V — and its scatter overwrites the
    # draft K/V with target-precision values.
    q_lens = jnp.where(valid, n_draft + 1, 0).astype(jnp.int32)
    x, pools = chunk_forward(
        params, wtok, lengths, q_lens, tables, *pools,
        cfg=cfg, mesh=mesh, verify=True,
    )
    logits = dense(x, params["unembed"]).astype(jnp.float32)  # [B, C, V]
    logits = jnp.where(jnp.arange(logits.shape[-1]) < cfg.vocab, logits, -1e30)

    if greedy:
        # exact-equality accept: emitted tokens ARE the target argmaxes
        tgt = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, C]
        dr = wtok[:, 1:]
        in_window = (
            jnp.arange(spec_k, dtype=jnp.int32)[None, :] < n_draft[:, None]
        )
        match = (dr == tgt[:, :-1]) & in_window
        accept = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
        return tgt, accept, pools

    c = spec_k + 1
    rep = lambda a: None if a is None else jnp.repeat(a, c)
    q_tgt = ops.sampling_probs(
        logits.reshape(b * c, -1), rep(temps), rep(top_ks), rep(top_ps)
    ).reshape(b, c, -1)

    # --- fused speculative rejection sampling
    q_draft = (
        jnp.stack(draft_probs, axis=1)
        if spec_k
        else jnp.zeros((b, 0) + (logits.shape[-1],), q_tgt.dtype)
    )
    emit, accept = rejection_sample(
        wtok[:, 1:], q_draft, q_tgt, seeds, pos0, n_draft
    )
    return emit, accept, pools


def plan_windows(
    reqs, capacities: np.ndarray, spec_k: int
) -> np.ndarray:
    """Per-row draft budget for one round: each request drafts at most its
    own ``spec_k``, clipped so the round (a) never emits past
    ``max_new_tokens`` (mid-window budget clipping — the verify's bonus
    token occupies one slot) and (b) never writes past the pages the pool
    could actually grant (``_ensure_page_room`` degrades under pressure
    rather than evicting for speculation)."""
    n_draft = np.zeros(len(reqs), np.int32)
    for i, r in enumerate(reqs):
        remaining = r.max_new_tokens - len(r.out_tokens)
        room = int(capacities[i]) - r.cache_len - 1  # window writes n_draft+1
        n_draft[i] = max(0, min(r.spec_k, spec_k, remaining - 1, room))
    return n_draft


def clip_stop(req, emitted: list[int]) -> tuple[list[int], bool]:
    """Mid-window stop-token clipping: cut ``emitted`` after the first stop
    token (kept, like plain decode keeps it).  Returns (tokens, stopped)."""
    for j, tok in enumerate(emitted):
        if req.is_stop(tok):
            return emitted[: j + 1], True
    return emitted, False
