"""KV-cache utilities shared by all attention archs.

Two layouts:
  * linear cache: (B, Smax, Hkv, D) with write at ``pos`` — train-free decode
    up to Smax (decode_32k).
  * ring cache (sliding-window archs at long_500k): (B, W, Hkv, D); slot
    ``pos % W``; the slot->absolute-position map is recomputed analytically,
    so memory is O(W) not O(S) — the sub-quadratic carve-in of DESIGN.md §4.

Batched slot-table layout (the serve engine)
--------------------------------------------
The batch dim doubles as the SLOT TABLE of the continuous-batching engine:
row ``b`` of every cache leaf is the private context of one in-flight
request, and requests sit at *different* depths. ``pos`` is therefore
allowed to be a per-row **vector** (B,) everywhere below, not just a
scalar:

  * ``write_kv`` scatters row-wise — ``k[arange(B), pos[b]] = k_new[b]`` —
    so one device program writes every slot's next token at its own cursor;
  * ``ring_kpos`` broadcasts over a (B, 1) position to give the per-row
    slot->absolute map (B, W);
  * the attention mask (layers.attention_full) takes per-row
    ``q_offset``/``kv_len``/``kpos`` so each row attends exactly to its own
    valid prefix.

A model-level ``cache["pos"]`` stays a scalar for the lockstep paths
(greedy_generate, dry-runs); the engine keeps its own (slots,) vector and
passes it to ``decode_step`` directly.

Paged layout (vLLM-style block tables; arXiv:2309.06180)
--------------------------------------------------------
The third layout drops per-slot rows entirely: one flat POOL of
fixed-size pages ``(layers, n_pages, Hkv, page_size, D)`` shared by every
slot, plus a per-slot page table ``ptab`` (slots, P) of pool indices that
maps logical block ``p // page_size`` of slot ``b`` to a physical page.
Token ``p`` of slot ``b`` therefore lives at
``pool[ptab[b, p // page_size], :, p % page_size]``. The pool is
HEAD-MAJOR — each page holds one contiguous ``(page_size, D)`` tile per
KV head — because that is the block the TPU kernel
(kernels/paged_attention.py) can fetch: a block's last two dims must be
the pool's own or tile-aligned, and a token-major page would put the
head axis second-to-last.

  * ``write_kv_paged`` scatters each row's decode token through the table
    at its own cursor — still ONE device program for the whole slot table;
  * ``layers.paged_attention`` gathers ``pool[ptab[b]]`` into position
    order (``gather_pages``), then masks to each row's live prefix —
    identical math to the dense path, so paged and dense decode are
    token-identical for row-independent (non-MoE) archs; MoE capacity
    routing couples slot rows either way, and the layouts feed inactive
    rows different scratch, so batched MoE keeps its existing
    occupancy-dependence caveat across layouts;
  * page id 0 is the NULL page: inactive slots and bucket padding write
    there harmlessly, and table entries beyond a slot's reservation point
    at it (always masked by ``kv_len``).

WHICH pages a slot owns is host-side bookkeeping
(``serve/paging.PageAllocator``); the device never sees the free-list,
only the table values, so admission/churn never retraces the step.

Prefix sharing (serve/prefix.py) rides on the same property: a page may
appear in SEVERAL slots' table rows (a common prompt prefix held once),
and only table values change, so decode still traces exactly once. The
one device-side addition is ``copy_page`` — the copy-on-write step that
duplicates a shared page's contents before a writer appends into it.

Sharded (TP) pool layout: under a ("data", "model") mesh the pool keeps
this exact shape but is partitioned on the KV-HEAD axis —
``(L, n_pages, Hkv/tp, page_size, D)`` per device
(core/sharding.cache_pspecs) — so every device holds its head slice of
EVERY page and each resident page costs 1/tp per device. Page ids stay
global (the page/table axes are never sharded: a table lookup must
resolve on every device), which is why the whole serve bookkeeping —
allocator, prefix cache, preemption — is sharding-blind: it only ever
deals in page ids and table values.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def init_kv(batch: int, length: int, n_kv: int, head_dim: int, dtype):
    shape = (batch, length, n_kv, head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def init_paged_kv(layers: int, n_pages: int, page_size: int, n_kv: int,
                  head_dim: int, dtype):
    """Flat head-major page pool shared by every slot:
    ``(layers, n_pages, n_kv, page_size, head_dim)``. ``n_pages`` INCLUDES
    the null page 0 (so a pool serving K usable pages has n_pages = K + 1)."""
    shape = (layers, n_pages, n_kv, page_size, head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def write_kv_paged(cache, k_new, v_new, page_table, pos):
    """Scatter one decode token per slot through the page table.

    cache leaves: (n_pages, Hkv, page_size, D) — ONE layer's pool (models
    scan over the stacked layer axis). k_new/v_new: (B, 1, Hkv, D);
    page_table: (B, P) pool indices; pos: (B,) per-row cursors. Inactive
    slots resolve to the null page 0 (their table rows are zeroed and the
    block index is clipped), so the scatter is total — no masking branch,
    no retrace.
    """
    page_size = cache["k"].shape[2]
    pos = jnp.asarray(pos)
    rows = jnp.arange(page_table.shape[0])
    blk = jnp.clip(pos // page_size, 0, page_table.shape[1] - 1)
    page = page_table[rows, blk]
    off = pos % page_size
    cache = dict(cache)
    # (page, :, off) indexing puts the row axis first: (B, Hkv, D)
    cache["k"] = cache["k"].at[page, :, off].set(k_new[:, 0])
    cache["v"] = cache["v"].at[page, :, off].set(v_new[:, 0])
    return cache


def scatter_prefill_pages(pool, kvs, pages, page_size: int):
    """Write a freshly-prefilled per-request KV into its pool pages.

    pool leaves: (L, n_pages, Hkv, page_size, D) — the engine's shared
    head-major page pool. kvs leaves: (L, 1, S, Hkv, D) with S a
    multiple of ``page_size`` (the prefill cache is sized to whole
    pages). pages: (S // page_size,) pool indices — entries beyond the
    slot's reservation are the null page 0, so bucket padding lands in
    scratch instead of a neighbour's page.
    """
    def put(pool_leaf, kv_leaf):
        l, _, s, h, d = kv_leaf.shape
        tiles = kv_leaf.reshape(l, s // page_size, page_size, h, d)
        return pool_leaf.at[:, pages].set(tiles.swapaxes(2, 3))

    return jax.tree.map(put, pool, kvs)


def copy_page(pool, src, dst):
    """Copy one physical page's contents, all layers at once — the device
    half of copy-on-write (the allocator swaps the table entry, this moves
    the KV). pool leaves: (L, n_pages, Hkv, page_size, D); src/dst:
    scalar page ids (traced values, so ONE program covers every copy)."""
    return {"k": pool["k"].at[:, dst].set(pool["k"][:, src]),
            "v": pool["v"].at[:, dst].set(pool["v"][:, src])}


def gather_pages(pool, page_table):
    """pool: (n_pages, Hkv, page_size, D); page_table: (B, P) ->
    contiguous per-row KV (B, P * page_size, Hkv, D) in logical position
    order.

    The gather indexes the pool's token-major view (n_pages, page_size,
    Hkv, D), so its output is already in position order. On the TPU the
    compiler lays a layer's pool out token-major for the per-token K/V
    write, which makes the view free: the compiled mixed step transposes
    neither the gathered pages nor, inside the live-row switch of
    layers.paged_attention, the pool."""
    b, p = page_table.shape
    _, hkv, page_size, d = pool.shape
    out = pool.swapaxes(1, 2)[page_table]       # (B, P, page_size, Hkv, D)
    return out.reshape(b, p * page_size, hkv, d)


def ring_slot(pos, window: int):
    return pos % window


def ring_kpos(pos, window: int):
    """Absolute position held by each ring slot at time ``pos`` (may be <0
    for not-yet-filled slots; the attention mask drops those).

    ``pos`` scalar -> (W,); ``pos`` (B, 1) -> (B, W) per-row maps."""
    i = jnp.arange(window)
    return pos - ((pos - i) % window)


def fit_prefill(k, w: int):
    """Fit freshly-computed prefill K or V (B,S,Hkv,D) into a cache of
    length ``w``.  S >= w: keep the last w, rolled so absolute position
    ``p`` lands in ring slot ``p % w`` (the invariant ``ring_kpos``
    assumes — a no-op when S % w == 0, but required for arbitrary prompt
    lengths).  S < w: place at the front and zero-pad the tail (linear
    layout, also ring-consistent since p < w)."""
    s = k.shape[1]
    if s >= w:
        return jnp.roll(k[:, -w:], s % w, axis=1)
    return jnp.pad(k, ((0, 0), (0, w - s), (0, 0), (0, 0)))


def write_kv(cache, k_new, v_new, pos, *, ring: bool = False, window: int = 0):
    """k_new/v_new: (B, 1, Hkv, D); pos: scalar int32 (lockstep write at one
    cursor) or (B,) int32 (per-row cursors — the slot-table scatter)."""
    pos = jnp.asarray(pos)
    idx = ring_slot(pos, window) if ring else pos
    cache = dict(cache)
    if idx.ndim == 0:
        cache["k"] = cache["k"].at[:, idx].set(k_new[:, 0])
        cache["v"] = cache["v"].at[:, idx].set(v_new[:, 0])
    else:
        rows = jnp.arange(cache["k"].shape[0])
        cache["k"] = cache["k"].at[rows, idx].set(k_new[:, 0])
        cache["v"] = cache["v"].at[rows, idx].set(v_new[:, 0])
    return cache
