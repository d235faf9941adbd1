"""Flash attention as a Pallas TPU kernel.

TPU adaptation of the GPU flash algorithm (DESIGN.md §3): instead of a
warp-level softmax with shared-memory tiles, we tile HBM->VMEM with
BlockSpecs sized for the MXU (q/k blocks are multiples of 128 in the lane
dim) and carry the online-softmax state (m, l, acc) in VMEM scratch across
the *sequential* kv grid dimension. Causality is handled per-block: fully
masked blocks are skipped with ``pl.when`` (the compute saving the XLA
"masked" baseline cannot express).

GQA runs natively: query-head program ``bh`` reads KV row
``q_head // rep`` through the BlockSpec index map, so grouped KV is never
repeated to Hq width in HBM. Ragged sequence lengths are padded up to the
block grid and the tail masked with the same kv-bound helper the paged
decode kernel (kernels/paged_attention.py) uses for its last page.

Grid: (batch*q_heads, nq, nk) with nk innermost/sequential.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def pad_to_block(x, axis: int, block: int):
    """Zero-pad ``x`` along ``axis`` up to the next multiple of
    ``block`` (no-op when it already divides). The pad positions carry
    garbage logits downstream, so every consumer must mask them with
    :func:`kv_bound_mask` / slice them off the output."""
    n = x.shape[axis]
    extra = (-n) % block
    if extra == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, extra)
    return jnp.pad(x, widths)


def kv_bound_mask(kpos, kv_len):
    """True where a KV position is live: ``kpos < kv_len``. Shared
    between the flash kernel's ragged-tail masking (``kv_len`` = the
    real, pre-padding T) and the paged decode kernel's last-page /
    null-page masking (``kv_len = pos + 1``)."""
    return kpos < kv_len


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            causal: bool, window: int, block_q: int, block_k: int,
            nk: int, t_real: int, sm_scale: float):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k
    # block-level relevance: skip fully-masked (future / out-of-window /
    # ragged-pad) blocks
    relevant = k_start < t_real
    if causal:
        relevant = jnp.logical_and(relevant,
                                   k_start <= q_start + block_q - 1)
    if window > 0:
        relevant = jnp.logical_and(
            relevant, k_start + block_k - 1 > q_start - window)

    @pl.when(relevant)
    def _compute():
        q = q_ref[0].astype(jnp.float32)              # (bq, d)
        k = k_ref[0].astype(jnp.float32)              # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # (bq, bk)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 1)
        mask = kv_bound_mask(kpos, t_real)            # ragged pad tail
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=1)
        m_ref[...] = m_new
        acc_ref[...] = (acc_ref[...] * corr[:, None]
                        + jax.lax.dot_general(
                            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / safe[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q (B,S,Hq,D), k/v (B,T,Hkv,D) -> (B,S,Hq,D).

    GQA (Hq a multiple of Hkv) maps each query head to its KV head in
    the kernel's index map — callers never pre-repeat. S/T need not
    divide the block sizes: ragged tails are padded to the grid and
    masked (kv) / sliced (q) away.
    """
    b, s, hq, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    if hq % hkv:
        raise ValueError(
            f"query heads ({hq}) must be a multiple of KV heads ({hkv})")
    rep = hq // hkv
    block_q = min(block_q, s)
    block_k = min(block_k, t)
    q = pad_to_block(q, 1, block_q)
    k = pad_to_block(k, 1, block_k)
    v = pad_to_block(v, 1, block_k)
    sp, tp = q.shape[1], k.shape[1]
    nq, nk = sp // block_q, tp // block_k

    # (B,S,H,D) -> (B*H, S, D) for a clean 3-D blocking; KV keeps Hkv rows
    qr = q.transpose(0, 2, 1, 3).reshape(b * hq, sp, d)
    kr = k.transpose(0, 2, 1, 3).reshape(b * hkv, tp, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * hkv, tp, d)

    kernel = functools.partial(
        _kernel, causal=causal, window=window, block_q=block_q,
        block_k=block_k, nk=nk, t_real=t, sm_scale=1.0 / np.sqrt(d))

    # GQA head fold: query-head program bh = batch*hq + qh reads KV row
    # (batch, qh // rep) — same mapping as the paged decode kernel
    def _kv_row(bh):
        return (bh // hq) * hkv + (bh % hq) // rep

    out = pl.pallas_call(
        kernel,
        grid=(b * hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, qi, ki: (_kv_row(bh), ki, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, qi, ki: (_kv_row(bh), ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d),
                               lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sp, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),        # running max m
            pltpu.VMEM((block_q,), jnp.float32),        # running sum l
            pltpu.VMEM((block_q, d), jnp.float32),      # accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, hq, sp, d).transpose(0, 2, 1, 3)[:, :s]
