"""Serving steps: prefill and single-token decode (the dry-run targets for
prefill_32k / decode_32k / long_500k), prompt-length and page-table
bucketing, the mixed step's token-budget packing, and the
greedy/sampled generate loop."""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core.strategy import Strategy
from repro.models import get_model
from repro.serve.sampling import sample_tokens

MIN_PREFILL_BUCKET = 8


def prefill_bucket(n: int, *, cap: int = 0,
                   min_bucket: int = MIN_PREFILL_BUCKET) -> int:
    """Padded prompt length for an ``n``-token prompt: the smallest
    power of two >= max(n, min_bucket).

    Bucketing bounds the number of distinct prefill shapes — and therefore
    XLA retraces — to log2(max_len) instead of one per prompt length.
    ``cap`` > 0 bounds the padded length (the cache window); when even the
    bucket would overflow it, fall back to the exact length so the cache
    layout stays consistent (``kvcache.fit_prefill`` would otherwise keep
    padding rows and drop real ones).
    """
    assert n >= 1
    b = max(n, min_bucket)
    b = 1 << (b - 1).bit_length()
    if cap > 0 and b > cap:
        return n
    return b


def page_bucket(n_blocks: int, *, cap: int) -> int:
    """Bucketed page-table width for the engine's bounded paged-attention
    gather: the smallest power of two >= ``n_blocks`` (the allocator's
    per-owner page high-water mark), clipped to ``cap`` (the full table
    width, ``pages_for(max_len)``). Bucketing means the decode program
    only retraces when occupancy crosses a power-of-two block boundary —
    cost tracks the pool's live high-water mark, not ``max_len``, while
    the one-decode-trace property holds between re-bucketings."""
    assert n_blocks >= 1 and cap >= 1
    return min(cap, 1 << (n_blocks - 1).bit_length())


def pack_token_budget(budget: int, decode_rows, prefill_items):
    """Fill one mixed step's token budget: decode first, then prefill
    chunks in the given order (the scheduler's priority order).

    ``decode_rows`` is either the total decode row count (the classic
    one-row-per-slot step) or a sequence of PER-SLOT row counts — the
    speculative-decode hook: a slot verifying ``k`` drafted tokens
    occupies ``1 + k`` rows (its base decode row plus the draft rows),
    and every one of them is reserved ahead of prefill. Only the sum
    matters to the packing; the sequence form exists so callers state
    per-slot demand directly and the property tests can pin that drafted
    rows are never displaced.

    ``prefill_items`` are dicts with ``slot``, ``cursor`` (prompt tokens
    already prefilled), ``n`` (total prompt tokens) and optional ``dep``
    — a ``(donor_slot, needed_tokens)`` pair meaning this item adopted
    the donor's shared pages up to ``needed_tokens`` and must not run a
    chunk until the donor's PLANNED coverage (its cursor after this
    step's allotments) reaches that point; same-step coverage counts
    because the mixed program scatters every chunk's KV before any token
    attends (serve/engine._mixed_fn).

    Returns ``[(slot, start, count), ...]`` with ``count >= 1``,
    ``sum(count) <= budget - sum(decode_rows)``. Decode (and draft) rows
    are reserved FIRST — prefill never displaces them — and a step whose
    decode demand alone exceeds the budget is a sizing bug, so it
    raises. Pure host logic; the hypothesis suite in
    tests/test_serve_mixed.py drives it across random mixes.
    """
    n_decode = decode_rows if isinstance(decode_rows, int) \
        else sum(decode_rows)
    if n_decode > budget:
        raise ValueError(
            f"decode demand {n_decode} exceeds the token budget {budget}; "
            "chunk_tokens must be >= the slot count")
    left = budget - n_decode
    planned_end = {it["slot"]: it["cursor"] for it in prefill_items}
    allot = []
    for it in prefill_items:
        if left <= 0:
            break
        dep = it.get("dep")
        if dep is not None:
            donor, needed = dep
            if planned_end.get(donor, needed) < needed:
                continue
        take = min(left, it["n"] - it["cursor"])
        if take <= 0:
            continue
        allot.append((it["slot"], it["cursor"], take))
        planned_end[it["slot"]] = it["cursor"] + take
        left -= take
    return allot


def make_prefill_step(cfg, strategy: Strategy) -> Callable:
    model = get_model(cfg)
    n_micro = strategy.microbatches

    def one(params, batch):
        cache = model.init_cache(cfg, batch["tokens"].shape[0],
                                 batch["tokens"].shape[1])
        return model.prefill(params, batch, cfg, cache,
                             attn_impl=strategy.attn_impl)

    def prefill_step(params, batch):
        b = batch["tokens"].shape[0]
        if n_micro <= 1 or b % n_micro != 0:
            return one(params, batch)
        # batch-chunked prefill: bounds the transient activation /
        # MoE-dispatch working set to one chunk (beyond-paper; §Perf).
        micro = jax.tree.map(
            lambda x: x.reshape(n_micro, b // n_micro, *x.shape[1:]), batch)
        logits, caches = jax.lax.map(lambda mb: one(params, mb), micro)
        # (n, ..., b/n, ...) -> merge the chunked batch dim (dim 0 of
        # logits; dim 1 of stacked (L, b, ...) cache leaves; pos is scalar)
        logits = logits.reshape(b, *logits.shape[2:])

        def merge(leaf):
            if leaf.ndim <= 1:          # pos scalars: identical per chunk
                return leaf[0]
            # (n, L, b/n, ...) -> (L, n, b/n, ...) -> (L, b, ...)
            moved = jnp.moveaxis(leaf, 0, 1)
            return moved.reshape(moved.shape[0], b, *moved.shape[3:])

        cache = jax.tree.map(merge, caches)
        return logits, cache

    return prefill_step


def make_decode_step(cfg, strategy: Strategy) -> Callable:
    """serve_step: ONE new token against a cache of seq_len entries."""
    model = get_model(cfg)

    def decode_step(params, cache, token, pos):
        return model.decode_step(params, cache, token, pos, cfg)

    return decode_step


def greedy_generate(params, cfg, strategy, prompt, steps: int, *,
                    temperature: float = 0.0,
                    rng: Optional[jax.Array] = None):
    """Simple lockstep generate loop used by examples/tests (jit per step).

    Greedy by default; ``temperature > 0`` (+ ``rng``) samples through the
    same on-device hook the serve engine uses (serve/sampling.py)."""
    model = get_model(cfg)
    b, s = prompt["tokens"].shape
    cache = model.init_cache(cfg, b, s + steps)
    logits, cache = model.prefill(params, prompt, cfg, cache)
    keys = (jax.random.split(rng, steps) if rng is not None
            else [None] * steps)
    tok = sample_tokens(logits[:, -1], rng=keys[0],
                        temperature=temperature)[:, None]
    out = [tok]
    step_fn = jax.jit(
        lambda p_, c, t, i, k: _sampled_decode(model, cfg, p_, c, t, i, k,
                                               temperature))
    for i in range(steps - 1):
        tok, cache = step_fn(params, cache, tok,
                             jnp.asarray(s + i, jnp.int32), keys[i + 1])
        out.append(tok)
    return jnp.concatenate(out, axis=1)


def _sampled_decode(model, cfg, params, cache, tok, pos, rng, temperature):
    logits, cache = model.decode_step(params, cache, tok, pos, cfg)
    return sample_tokens(logits[:, -1], rng=rng,
                         temperature=temperature)[:, None], cache
