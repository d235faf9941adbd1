"""Core layers: norms, RoPE, GQA attention (full / chunked / sliding-window),
gated + plain MLPs.

Attention has three execution paths:
  * "full":     materialise (B,H,S,T) scores — small shapes / tests only.
  * "chunked":  double-blocked online-softmax (flash-style in pure XLA) —
                the default HLO path for big shapes; memory O(S*Ck) not O(S^2).
  * Pallas:     kernels/flash_attention.py — the TPU target, selected by
                ops-level dispatch, validated vs ref in interpret mode.

``window > 0`` gives sliding-window attention: each query attends to the
previous ``window`` positions only; the chunked path then visits a STATIC
number of KV chunks per query chunk => sub-quadratic compute (DESIGN.md §4).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.pspec import constrain
from repro.models.kvcache import gather_pages

# ---------------------------------------------------------------- init utils

def dense_init(key, shape, dtype, scale: float = 0.02):
    return (scale * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


# --------------------------------------------------------------------- norms

def rmsnorm(x, scale, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * inv * scale).astype(x.dtype)


def layernorm(x, scale, bias, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean((x32 - mu) ** 2, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


# ---------------------------------------------------------------------- RoPE

def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(d, theta), jnp.float32)      # (D/2,)
    ang = positions[..., :, None].astype(jnp.float32) * freqs   # (..., S, D/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------- attention

NEG_INF = -1e30


def _repeat_kv(k, n_rep: int):
    if n_rep == 1:
        return k
    b, t, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, t, h, n_rep, d)
                            ).reshape(b, t, h * n_rep, d)


def attention_full(q, k, v, *, causal: bool, window: int = 0,
                   q_offset: int = 0, kv_len: Optional[jnp.ndarray] = None,
                   kpos: Optional[jnp.ndarray] = None):
    """Reference/small-shape path. q:(B,S,Hq,D) k,v:(B,T,Hkv,D) -> (B,S,Hq,D).

    q_offset: absolute position of q[0] (decode: q_offset = pos). Scalar, or
              (B,) for the slot-table decode where every row sits at its own
              depth.
    kv_len: optional dynamic valid length of the KV (decode cache fill
            level); scalar or per-row (B,).
    kpos:   optional absolute position per KV slot (ring caches); (T,) or
            per-row (B, T); entries < 0 are masked out.
    """
    b, s, hq, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    rep = hq // hkv
    # GQA without repeating K/V to Hq heads: query head g*rep + r reads
    # kv head g (the _repeat_kv mapping), contracted group-wise, so the
    # paged gather's (B, T, Hkv, D) K/V are never widened rep-fold
    qg = q.reshape(b, s, hkv, rep, d)
    scores = jnp.einsum("bsgrd,btgd->bgrst", qg, k,
                        preferred_element_type=jnp.float32
                        ).reshape(b, hq, s, t)
    scores *= 1.0 / np.sqrt(d)
    # per-row broadcasting: qpos (1|B, S), kpos (1|B, T) -> mask (1|B, S, T)
    qpos = jnp.asarray(q_offset).reshape(-1, 1) + jnp.arange(s)
    if kpos is None:
        kpos = jnp.arange(t)
    kpos = jnp.asarray(kpos)
    kpos = kpos[None, :] if kpos.ndim == 1 else kpos
    mask = jnp.ones((1, s, t), bool)
    if causal:
        mask = mask & (kpos[:, None, :] <= qpos[:, :, None])
    if window > 0:
        mask &= kpos[:, None, :] > qpos[:, :, None] - window
    if kv_len is not None:
        mask &= kpos[:, None, :] < jnp.asarray(kv_len).reshape(-1, 1, 1)
    mask &= kpos[:, None, :] >= 0
    scores = jnp.where(mask[:, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bgrst,btgd->bsgrd", probs.reshape(b, hkv, rep, s, t),
                     v)
    return out.reshape(b, s, hq, d)


def row_widths(t: int) -> tuple:
    """The static ladder of row counts a ``rows``-cut paged attention can
    run at for a batch of ``t`` rows: powers of two from 8 up to ``t``,
    with ``t`` itself on top (8, 16, ..., 256 for ``t`` = 256; (4,) for
    ``t`` = 4)."""
    widths, w = [], 8
    while w < t:
        widths.append(w)
        w *= 2
    return tuple(widths) + (t,)


def row_width(rows: int, t: int) -> int:
    """The ladder's smallest width holding ``rows`` live rows (host side:
    the width :func:`paged_attention` runs for that count)."""
    return next(w for w in row_widths(t) if w >= rows)


def paged_attention(q, k_pages, v_pages, page_table, pos, *,
                    backend: str = "gather", rows=None):
    """Decode-time block-table attention over a paged KV pool (vLLM-style).

    q: (B, 1, Hq, D) — one fresh token per slot-table row.
    k_pages/v_pages: (n_pages, Hkv, page_size, D) — the flat shared
    head-major pool (models/kvcache.py).
    page_table: (B, P) pool indices per row (0 = null page).
    pos: (B,) per-row cursors (tokens already in context, incl. this one's
    write — the query attends to positions [0, pos]).
    rows: optional traced int32 scalar, the live-row contract: rows
    ``[0, rows)`` are live and rows ``[rows, B)`` are pads whose output
    nobody reads. The attention then runs only over the first ``Tb``
    rows, ``Tb`` the smallest width of :func:`row_widths` (B) holding
    ``rows``, one ``lax.switch`` branch per width, and output rows
    ``[rows, B)`` are zeros. Shapes stay static, so this adds no trace;
    the widest branch is the uncut computation. ``None`` (default) runs
    every row.

    Two backends, token-identical greedy outputs:

    ``backend="gather"`` (default) gathers each row's pages in logical-
    block order, so the gathered axis IS the position axis and the dense
    mask machinery applies unchanged: ``kv_len = pos + 1`` hides
    null/garbage tail pages. The gather is a table lookup — table VALUES
    change between steps, shapes never do, so the batched decode program
    still traces exactly once. It materializes ``P * page_size``
    positions per row per layer, where ``P`` is the WIDTH OF THE TABLE
    PASSED IN — the serve engine hands this function a table clipped to
    the power-of-two bucket of the allocator's per-slot page high-water
    mark (serve/step.page_bucket), so decode cost tracks pool occupancy
    rather than ``max_len`` and the program only retraces when the
    high-water crosses a bucket boundary.

    ``backend="pallas"`` runs the fused flash-decoding kernel
    (kernels/paged_attention.py): one grid block per page with online
    softmax carried across the page axis, the pool indexed through the
    scalar-prefetched table — contiguous KV is never materialized and
    GQA heads fold in-kernel. Same masking (``kv_len = pos + 1``), same
    trace cadence (shapes depend only on the bucketed table width); on
    CPU it runs in interpret mode (kernels/ops.interpret_mode).

    TP note: under a ("data", "model") mesh the pool is head-sharded
    over "model" (core/sharding.cache_pspecs) — both backends index the
    unsharded page axis and stay head-local per device (each sees its
    own Hkv/tp heads) until the row-sharded output projection's
    all-reduce; the row cut slices the replicated batch axis only.
    """
    if backend not in ("gather", "pallas"):
        raise ValueError(
            f"paged_attention backend must be 'gather' or 'pallas', "
            f"got {backend!r}")

    def attend(q, k_pages, v_pages, page_table, pos):
        if backend == "pallas":
            from repro.kernels import ops as kops
            return kops.paged_attention(q, k_pages, v_pages, page_table,
                                        pos)
        kv_len = jnp.asarray(pos) + 1
        k = gather_pages(k_pages, page_table)
        v = gather_pages(v_pages, page_table)
        return attention_full(q, k, v, causal=True, q_offset=pos,
                              kv_len=kv_len)

    def cut(w):
        if w == b:
            return attend

        def branch(q, k_pages, v_pages, page_table, pos):
            out = attend(q[:w], k_pages, v_pages, page_table[:w], pos[:w])
            return jnp.pad(out, ((0, b - w), (0, 0), (0, 0), (0, 0)))
        return branch

    with jax.named_scope("attention"):
        if rows is None:
            return attend(q, k_pages, v_pages, page_table, pos)
        b = q.shape[0]
        widths = row_widths(b)
        # index of the smallest width >= rows (the top one past them all)
        idx = jnp.sum(jnp.asarray(widths[:-1]) < rows)
        out = jax.lax.switch(idx, [cut(w) for w in widths], q, k_pages,
                             v_pages, page_table, pos)
        live = jnp.arange(b) < rows
        return jnp.where(live[:, None, None, None], out, 0)


def _attn_block(q, k, v, qpos, kpos, scale, causal, window, m, l, acc):
    """One (q-chunk, kv-chunk) online-softmax update. fp32 carries."""
    s = jnp.einsum("bshd,bthd->bhst", q, k,
                   preferred_element_type=jnp.float32) * scale
    mask = jnp.ones((q.shape[1], k.shape[1]), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window > 0:
        mask &= kpos[None, :] > qpos[:, None] - window
    s = jnp.where(mask[None, None], s, NEG_INF)
    m_new = jnp.maximum(m, s.max(-1))                    # (B,H,Sq)
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l_new = l * corr + p.sum(-1)
    acc_new = acc * corr[..., None] + jnp.einsum(
        "bhst,bthd->bhsd", p, v.astype(jnp.float32))
    return m_new, l_new, acc_new


def attention_chunked(q, k, v, *, causal: bool, window: int = 0,
                      q_chunk: int = 1024, kv_chunk: int = 1024,
                      impl: str = "masked"):
    """Double-blocked flash-style attention in pure XLA.

    impl="masked":   every (qi, ki) block pair is computed, causally-dead
                     blocks masked out (paper-faithful naive baseline; HLO
                     FLOPs ~2x the causal ideal).
    impl="triangle": only lower-triangle block pairs are computed (static
                     pair list) — the beyond-paper compute optimisation.
    For window>0 each q chunk visits a STATIC slice of the KV of length
    window+q_chunk => sub-quadratic.
    """
    b, s, hq, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    k, v = _repeat_kv(k, hq // hkv), _repeat_kv(v, hq // hkv)
    q_chunk, kv_chunk = min(q_chunk, s), min(kv_chunk, t)
    assert s % q_chunk == 0 and t % kv_chunk == 0
    nq, nk = s // q_chunk, t // kv_chunk
    scale = 1.0 / np.sqrt(d)

    qs = q.reshape(b, nq, q_chunk, hq, d)

    if window > 0:
        # static KV window per q chunk: [start, start + wlen)
        wlen = min(t, ((window + q_chunk + kv_chunk - 1) // kv_chunk) * kv_chunk)

        def one_q(qi, qc):
            start = jnp.clip(qi * q_chunk + q_chunk - wlen, 0, t - wlen)
            kc = jax.lax.dynamic_slice_in_dim(k, start, wlen, axis=1)
            vc = jax.lax.dynamic_slice_in_dim(v, start, wlen, axis=1)
            qpos = jnp.arange(q_chunk) + qi * q_chunk
            kpos = jnp.arange(wlen) + start
            m = jnp.full((b, hq, q_chunk), NEG_INF, jnp.float32)
            l = jnp.zeros((b, hq, q_chunk), jnp.float32)
            acc = jnp.zeros((b, hq, q_chunk, d), jnp.float32)
            m, l, acc = _attn_block(qc, kc, vc, qpos, kpos, scale,
                                    causal, window, m, l, acc)
            return (acc / l[..., None]).astype(q.dtype)

        out = jax.lax.map(lambda args: one_q(*args),
                          (jnp.arange(nq), qs.swapaxes(0, 1)))
        return out.transpose(1, 0, 3, 2, 4).reshape(b, s, hq, d)

    ks = k.reshape(b, nk, kv_chunk, hq, d)
    vs = v.reshape(b, nk, kv_chunk, hq, d)

    if impl == "triangle" and causal and nq == nk:
        # static lower-triangle pair list, grouped by q chunk
        def one_q(qi, qc):
            qpos = jnp.arange(q_chunk) + qi * q_chunk
            m = jnp.full((b, hq, q_chunk), NEG_INF, jnp.float32)
            l = jnp.zeros((b, hq, q_chunk), jnp.float32)
            acc = jnp.zeros((b, hq, q_chunk, d), jnp.float32)

            def body(ki, carry):
                m, l, acc = carry
                kc = jax.lax.dynamic_slice_in_dim(k, ki * kv_chunk, kv_chunk, 1)
                vc = jax.lax.dynamic_slice_in_dim(v, ki * kv_chunk, kv_chunk, 1)
                kpos = jnp.arange(kv_chunk) + ki * kv_chunk
                return _attn_block(qc, kc, vc, qpos, kpos, scale,
                                   causal, window, m, l, acc)

            m, l, acc = jax.lax.fori_loop(0, qi + 1, body, (m, l, acc))
            return (acc / l[..., None]).astype(q.dtype)

        out = jax.lax.map(lambda args: one_q(*args),
                          (jnp.arange(nq), qs.swapaxes(0, 1)))
        return out.transpose(1, 0, 3, 2, 4).reshape(b, s, hq, d)

    # masked baseline: all nq*nk block pairs
    def one_q(qi, qc):
        qpos = jnp.arange(q_chunk) + qi * q_chunk
        m = jnp.full((b, hq, q_chunk), NEG_INF, jnp.float32)
        l = jnp.zeros((b, hq, q_chunk), jnp.float32)
        acc = jnp.zeros((b, hq, q_chunk, d), jnp.float32)

        def body(carry, kvi):
            m, l, acc = carry
            kc, vc, ki = kvi
            kpos = jnp.arange(kv_chunk) + ki * kv_chunk
            m, l, acc = _attn_block(qc, kc, vc, qpos, kpos, scale,
                                    causal, window, m, l, acc)
            return (m, l, acc), None

        (m, l, acc), _ = jax.lax.scan(
            body, (m, l, acc),
            (ks.swapaxes(0, 1), vs.swapaxes(0, 1), jnp.arange(nk)))
        safe_l = jnp.where(l == 0, 1.0, l)
        return (acc / safe_l[..., None]).astype(q.dtype)

    out = jax.lax.map(lambda args: one_q(*args),
                      (jnp.arange(nq), qs.swapaxes(0, 1)))
    return out.transpose(1, 0, 3, 2, 4).reshape(b, s, hq, d)


def attention(q, k, v, *, causal: bool, window: int = 0, q_offset: int = 0,
              kv_len=None, kpos=None, impl: str = "auto", q_chunk: int = 1024,
              kv_chunk: int = 1024):
    """Dispatching entry point used by all models."""
    s, t = q.shape[1], k.shape[1]
    if impl == "triangle" and (not causal or s != t or s % q_chunk):
        impl = "auto"            # triangle needs a square causal grid
    if impl == "auto":
        impl = "full" if (s * t <= 2048 * 2048 or s == 1) else "chunked"
    if impl == "full" or s == 1 or kv_len is not None or kpos is not None:
        return attention_full(q, k, v, causal=causal, window=window,
                              q_offset=q_offset, kv_len=kv_len, kpos=kpos)
    if impl in ("chunked", "masked"):
        return attention_chunked(q, k, v, causal=causal, window=window,
                                 q_chunk=q_chunk, kv_chunk=kv_chunk,
                                 impl="masked")
    if impl == "triangle":
        return attention_chunked(q, k, v, causal=causal, window=window,
                                 q_chunk=q_chunk, kv_chunk=kv_chunk,
                                 impl="triangle")
    if impl == "pallas":
        from repro.kernels import ops as kops
        return kops.flash_attention(q, k, v, causal=causal, window=window)
    raise ValueError(impl)


# ------------------------------------------------------- attention (module)

def init_attn(key, cfg, *, cross: bool = False):
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    ks = jax.random.split(key, 6)
    p = {
        "wq": dense_init(ks[0], (d, qd), jnp.dtype(cfg.dtype)),
        "wk": dense_init(ks[1], (d, kvd), jnp.dtype(cfg.dtype)),
        "wv": dense_init(ks[2], (d, kvd), jnp.dtype(cfg.dtype)),
        "wo": dense_init(ks[3], (qd, d), jnp.dtype(cfg.dtype),
                         scale=0.02 / np.sqrt(2 * cfg.num_layers)),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((cfg.head_dim,), jnp.float32)
        p["k_norm"] = jnp.ones((cfg.head_dim,), jnp.float32)
    return p


def attn_qkv(p, x, cfg, *, positions=None, rope: bool = True):
    """Project to q,k,v (+qk_norm, +rope). x:(B,S,d) -> q(B,S,Hq,D), k/v."""
    b, s, _ = x.shape
    q = (x @ p["wq"]).reshape(b, s, cfg.num_heads, cfg.head_dim)
    k = (x @ p["wk"]).reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    v = (x @ p["wv"]).reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    if rope:
        if positions is None:
            positions = jnp.arange(s)[None, :]
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = constrain(q, "batch", None, "heads", None)
    k = constrain(k, "batch", None, "kv_heads", None)
    v = constrain(v, "batch", None, "kv_heads", None)
    return q, k, v


def attn_out(p, ctx, cfg):
    b, s = ctx.shape[:2]
    out = ctx.reshape(b, s, cfg.q_dim) @ p["wo"]
    return constrain(out, "batch", None, None)


# ----------------------------------------------------------------------- MLP

def init_mlp(key, cfg, *, d_ff: Optional[int] = None, gated: bool = True):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 3)
    p = {"w_up": dense_init(ks[1], (d, f), dt),
         "w_down": dense_init(ks[2], (f, d), dt,
                              scale=0.02 / np.sqrt(2 * cfg.num_layers))}
    if gated:
        p["w_gate"] = dense_init(ks[0], (d, f), dt)
    return p


def mlp(p, x, *, act=jax.nn.silu):
    """Gated (SwiGLU) if w_gate present else plain-GeLU MLP.

    This is the paper's §5.1 MLP: column-parallel first matmul(s) keep the
    nonlinearity local; row-parallel second matmul needs one all-reduce
    (generated by GSPMD from the shardings).
    """
    h = x @ p["w_up"]
    if "w_gate" in p:
        h = act(x @ p["w_gate"]) * h
    else:
        h = jax.nn.gelu(h)
    h = constrain(h, "batch", None, "d_ff")
    return constrain(h @ p["w_down"], "batch", None, None)
