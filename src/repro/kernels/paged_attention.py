"""Flash-decoding paged attention as a Pallas TPU kernel.

The gather path (models/layers.paged_attention with ``backend="gather"``)
materializes each row's ``P * page_size`` KV positions per layer through
XLA before attending — the memory-bound inefficiency hand-written decode
kernels exist to close. This kernel walks the page table directly: the
grid is one block PER PAGE with the online-softmax state (m, l, acc —
the same VMEM scratch pattern as kernels/flash_attention.py::_kernel)
carried across the sequential page axis, and the head-major pool
``(n_pages, Hkv, page_size, D)`` is indexed through the per-row table by
a scalar-prefetched BlockSpec index map — contiguous KV never exists.

Block layout: one K (and one V) block is a whole page, all of its KV
heads: ``(1, Hkv, page_size, D)``. Its last two dims are the pool's own
``(page_size, D)``, which the TPU lowering accepts for any page size and
head count (a head-sliced block of a token-major pool is refused: its
second-to-last dim would be one head out of Hkv). Each head's
``(page_size, D)`` tile is contiguous, so the per-head matmuls read
leading-dim slices only.

Contracts it shares with the gather path (the serve engine relies on
all three):

  * table VALUES are data, table SHAPE is static — one decode trace,
    retrace only when the engine's ``page_bucket`` width crosses;
  * ``kv_len = pos + 1`` masks everything behind each row's cursor, so
    null-page-0 entries (inactive slots, reservation tails, ragged last
    pages) contribute nothing — shared helper
    :func:`~repro.kernels.flash_attention.kv_bound_mask`. Pages past the
    cursor are neither computed nor fetched: their index map repeats the
    last live page, and the pipeline skips a block whose index did not
    change;
  * GQA query heads map to their ``q_head // rep`` KV head in-kernel
    (never pre-repeated), and every program stays head-local, so the
    tp head-sharded pool (core/sharding.cache_pspecs) composes: each
    shard's kernel sees its own Hkv/tp heads.

Grid: (B, P) with the page axis innermost/sequential; each program
handles every query head of one row against one page. The caller picks
interpret mode (kernels/ops.py: on the CPU only).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import NEG_INF, kv_bound_mask


def _kernel(tab_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, page_size: int, n_blocks: int,
            hkv: int, rep: int, sm_scale: float):
    """One row x one page, all KV heads. ``tab_ref``/``pos_ref`` are the
    scalar-prefetched page table (B, P) and cursors (B,) — the same
    table also drives the K/V BlockSpec index maps, which is what makes
    the pool lookup a block fetch instead of a gather."""
    row, p = pl.program_id(0), pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = pos_ref[row] + 1                   # query attends [0, pos]

    # skip pages entirely past the row's live prefix (null-page tail of
    # the bucketed table included — their positions are all >= kv_len)
    @pl.when(p * page_size < kv_len)
    def _compute():
        # page p covers positions [p*page_size, (p+1)*page_size): the
        # ragged last page masks exactly like the flash kernel's ragged
        # tail, via the shared kv-bound helper
        kpos = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (rep, page_size), 1)
        live = kv_bound_mask(kpos, kv_len)
        for h in range(hkv):                    # unrolled over KV heads
            q = q_ref[0, h].astype(jnp.float32)     # (rep, d)
            k = k_ref[0, h].astype(jnp.float32)     # (page_size, d)
            v = v_ref[0, h].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            s = jnp.where(live, s, NEG_INF)         # (rep, page_size)
            m_prev, l_prev = m_ref[h], l_ref[h]     # (rep, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p_exp = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_prev * corr + jnp.sum(p_exp, axis=1, keepdims=True)
            m_ref[h] = m_new
            acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
                p_exp, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(p == n_blocks - 1)
    def _finalize():
        l = l_ref[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / safe).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q, k_pages, v_pages, page_table, pos, *,
                    interpret: bool = False):
    """Fused paged-attention decode. Same contract as the gather path
    (models/layers.paged_attention):

    q: (B, 1, Hq, D) — one fresh token per row.
    k_pages/v_pages: (n_pages, Hkv, page_size, D) flat head-major pool
    (page 0 is the null page).
    page_table: (B, P) pool indices in logical-block order.
    pos: (B,) per-row cursors (or scalar, broadcast) — the query
    attends to positions [0, pos].

    -> (B, 1, Hq, D), with no ``(B, P*page_size, ...)`` intermediate.
    """
    b, _, hq, d = q.shape
    _, hkv, page_size, _ = k_pages.shape
    if hq % hkv:
        raise ValueError(
            f"query heads ({hq}) must be a multiple of KV heads ({hkv})")
    rep = hq // hkv
    n_blocks = page_table.shape[1]
    # group query heads by their KV head: consecutive q heads share one
    # kv head (the _repeat_kv layout), so this reshape IS the mapping
    qr = q.reshape(b, hkv, rep, d)
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))

    kernel = functools.partial(
        _kernel, page_size=page_size, n_blocks=n_blocks, hkv=hkv,
        rep=rep, sm_scale=1.0 / np.sqrt(d))

    def kv_index(row, p, tab, pos_r):
        # the PAGE axis block index comes from the prefetched table, so
        # the page streams straight from wherever the allocator put it.
        # Pages past the cursor map to the last live page: an unchanged
        # block index is not fetched again
        last = jnp.minimum(pos_r[row] // page_size, n_blocks - 1)
        return (tab[row, jnp.minimum(p, last)], 0, 0, 0)

    row_spec = pl.BlockSpec((1, hkv, rep, d),
                            lambda row, p, tab, pos_r: (row, 0, 0, 0))
    kv_spec = pl.BlockSpec((1, hkv, page_size, d), kv_index)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_blocks),
            in_specs=[row_spec, kv_spec, kv_spec],
            out_specs=row_spec,
            scratch_shapes=[
                pltpu.VMEM((hkv, rep, 1), jnp.float32),     # running max m
                pltpu.VMEM((hkv, rep, 1), jnp.float32),     # running sum l
                pltpu.VMEM((hkv, rep, d), jnp.float32),     # accumulator
            ]),
        out_shape=jax.ShapeDtypeStruct((b, hkv, rep, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(page_table.astype(jnp.int32), pos, qr, k_pages, v_pages)
    return out.reshape(b, 1, hq, d)
