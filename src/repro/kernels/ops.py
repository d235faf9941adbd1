"""jit'd wrappers around the Pallas kernels — the public ops API.

On a TPU the kernels compile to Mosaic; on the CPU they run with
``interpret=True`` (Pallas executes the kernel body as XLA ops), which is
how the CPU test suite covers them. :func:`interpret_mode` decides at
call time, from the backend JAX actually runs on: a TPU never takes the
interpreter, and any other backend is an error rather than a silent
fallback.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.fused_mlp import fused_mlp as _fused_mlp
from repro.kernels.paged_attention import paged_attention as _paged
from repro.kernels.ssd_scan import ssd_scan as _ssd_scan


def interpret_mode() -> bool:
    """True on the CPU (interpret), False on a TPU (compile); raises for
    any other backend, which has no Pallas TPU lowering."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas TPU kernels run compiled on a TPU or interpreted on the "
        f"CPU; the {backend!r} backend has neither")


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_trainable(q, k, v, causal, window, block_q, block_k):
    return _flash(q, k, v, causal=causal, window=window, block_q=block_q,
                  block_k=block_k, interpret=interpret_mode())


def _flash_fwd(q, k, v, causal, window, block_q, block_k):
    out = _flash_trainable(q, k, v, causal, window, block_q, block_k)
    return out, (q, k, v)


def _flash_bwd(causal, window, block_q, block_k, res, g):
    """Analytic backward via softmax recompute (pure jnp; on TPU this
    would be a second Pallas kernel — the math is identical). GQA: the
    recompute repeats KV to Hq width, then dk/dv group-sum back to Hkv —
    the transpose of the forward's in-kernel head fold."""
    q, k, v = res
    b, s, h, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    rep = h // hkv
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    s32 = jnp.einsum("bshd,bthd->bhst", q.astype(jnp.float32),
                     k.astype(jnp.float32)) / np.sqrt(d)
    qpos = jnp.arange(s)[:, None]
    kpos = jnp.arange(t)[None, :]
    mask = jnp.ones((s, t), bool)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    s32 = jnp.where(mask[None, None], s32, -1e30)
    p = jax.nn.softmax(s32, axis=-1)                        # (B,H,S,T)
    g32 = g.astype(jnp.float32)
    dv = jnp.einsum("bhst,bshd->bthd", p, g32)
    dp = jnp.einsum("bshd,bthd->bhst", g32, v.astype(jnp.float32))
    dsoft = p * (dp - jnp.sum(dp * p, axis=-1, keepdims=True))
    dsoft = dsoft / np.sqrt(d)
    dq = jnp.einsum("bhst,bthd->bshd", dsoft, k.astype(jnp.float32))
    dk = jnp.einsum("bhst,bshd->bthd", dsoft, q.astype(jnp.float32))
    if rep > 1:
        dk = dk.reshape(b, t, hkv, rep, d).sum(axis=3)
        dv = dv.reshape(b, t, hkv, rep, d).sum(axis=3)
    return dq.astype(q.dtype), dk.astype(res[1].dtype), \
        dv.astype(res[2].dtype)


_flash_trainable.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128):
    """GQA-aware entry: query heads map to their KV head inside the
    kernel's index map (no HBM repeat — k/v stay Hkv wide end to end).
    Differentiable (custom VJP)."""
    return _flash_trainable(q, k, v, causal, window, block_q, block_k)


def paged_attention(q, k_pages, v_pages, page_table, pos):
    """Fused flash-decoding paged attention: walks the page table with
    online softmax across the page axis — never materializes the
    gathered ``(B, P*page_size, ...)`` KV (kernels/paged_attention.py).
    Decode-only (no VJP): the serve engine's per-step program."""
    return _paged(q, k_pages, v_pages, page_table, pos,
                  interpret=interpret_mode())


def fused_mlp(x, w_gate, w_up, w_down, *, block_m: int = 256,
              block_f: int = 512):
    orig = x.shape
    x2 = x.reshape(-1, orig[-1])
    y = _fused_mlp(x2, w_gate, w_up, w_down, block_m=block_m,
                   block_f=block_f, interpret=interpret_mode())
    return y.reshape(orig)


def ssd_scan(x, dt, A, B, C, *, chunk: int = 128):
    return _ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=interpret_mode())
