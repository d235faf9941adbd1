"""Decoder-only transformer LM (llama-style): dense FFN or MoE FFN.

Covers assigned archs: deepseek-coder-33b, qwen3-14b, internlm2-20b,
minitron-4b (dense) and olmoe-1b-7b, kimi-k2-1t-a32b (MoE).

The layer stack is a ``lax.scan`` over parameters stacked on a leading L
axis, so HLO size and compile time are ~O(1) in depth (62-100 layer archs
compile in seconds — required for the 40x dry-run matrix).

Model API (shared by every family in models/):
  init(key, cfg)                                  -> params
  forward(params, batch, cfg, ...)                -> logits, aux
  init_cache(cfg, batch, max_len, dtype)          -> cache
  decode_step(params, cache, token, pos, cfg)     -> logits, cache
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.pspec import constrain
from repro.models import kvcache, moe as moe_lib
from repro.models.layers import (attention, attn_out, attn_qkv, dense_init,
                                 init_attn, init_mlp, mlp, paged_attention,
                                 rmsnorm)


# ----------------------------------------------------------------- init

def init_layer(key, cfg):
    ka, km = jax.random.split(key)
    p = {"attn": init_attn(ka, cfg),
         "ln1": jnp.ones((cfg.d_model,), jnp.float32),
         "ln2": jnp.ones((cfg.d_model,), jnp.float32)}
    if cfg.is_moe:
        p["moe"] = moe_lib.init_moe(km, cfg)
    else:
        p["mlp"] = init_mlp(km, cfg)
    return p


def init(key, cfg):
    ke, kl, kh = jax.random.split(key, 3)
    layers = jax.vmap(lambda k: init_layer(k, cfg))(
        jax.random.split(kl, cfg.num_layers))
    p = {
        "embed": dense_init(ke, (cfg.vocab_size, cfg.d_model),
                            jnp.dtype(cfg.dtype)),
        "layers": layers,
        "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(kh, (cfg.d_model, cfg.vocab_size),
                                  jnp.dtype(cfg.dtype))
    return p


# ----------------------------------------------------------------- blocks

def block(lp, x, cfg, *, attn_impl: str = "auto"):
    """Pre-norm attn + pre-norm FFN. Returns (y, aux_loss)."""
    h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
    q, k, v = attn_qkv(lp["attn"], h, cfg)
    ctx = attention(q, k, v, causal=True, window=cfg.sliding_window,
                    impl=attn_impl)
    x = x + attn_out(lp["attn"], ctx, cfg)
    h = rmsnorm(x, lp["ln2"], cfg.norm_eps)
    if cfg.is_moe:
        y, aux = moe_lib.moe_ffn(lp["moe"], h, cfg)
    else:
        y, aux = mlp(lp["mlp"], h), jnp.zeros((), jnp.float32)
    x = x + y
    return constrain(x, "batch", None, None), aux


def _embed(params, tokens, cfg):
    x = params["embed"][tokens]          # (B,S,d)
    return constrain(x.astype(jnp.dtype(cfg.dtype)), "batch", None, None)


def _head(params, x, cfg):
    with jax.named_scope("head"):
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        w = params["embed"].T if "lm_head" not in params \
            else params["lm_head"]
        logits = x @ w
        return constrain(logits, "batch", None, "vocab")


def forward(params, batch, cfg, *, remat: bool = False,
            attn_impl: str = "auto"):
    """batch: {"tokens": (B,S) int32}. Returns (logits (B,S,V), aux)."""
    x = _embed(params, batch["tokens"], cfg)

    def body(carry, lp):
        y, aux = block(lp, carry, cfg, attn_impl=attn_impl)
        return y, aux

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, auxs = jax.lax.scan(body, x, params["layers"])
    return _head(params, x, cfg), auxs.sum()


# ----------------------------------------------------------------- decode

def cache_window(cfg, max_len: int) -> int:
    """Ring-buffer length: SWA archs hold only the window."""
    if cfg.sliding_window > 0:
        return min(max_len, cfg.sliding_window)
    return max_len


def init_cache(cfg, batch: int, max_len: int, dtype=None):
    dtype = dtype or jnp.dtype(cfg.dtype)
    w = cache_window(cfg, max_len)
    one = kvcache.init_kv(batch, w, cfg.num_kv_heads, cfg.head_dim, dtype)
    return {
        "kv": jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.num_layers,) + a.shape), one),
        "pos": jnp.zeros((), jnp.int32),
    }


def prefill(params, batch, cfg, cache, *, attn_impl: str = "auto",
            last_pos=None):
    """Run the full prompt, fill the cache, return last-token logits.

    For ring (SWA) caches only the last ``window`` positions are retained.
    ``last_pos`` (scalar or (B,)): index of the last REAL token when the
    prompt is right-padded to a bucket length — logits are gathered there
    instead of at position S-1. Padding rows beyond ``last_pos`` are
    causally invisible to real rows and their (garbage) cache entries stay
    masked by ``kv_len``/``kpos`` until decode overwrites them.
    """
    tokens = batch["tokens"]
    b, s = tokens.shape
    w = cache["kv"]["k"].shape[2]
    x = _embed(params, tokens, cfg)

    def body(x, lp):
        h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
        q, k, v = attn_qkv(lp["attn"], h, cfg)
        ctx = attention(q, k, v, causal=True, window=cfg.sliding_window,
                        impl=attn_impl)
        x = x + attn_out(lp["attn"], ctx, cfg)
        h = rmsnorm(x, lp["ln2"], cfg.norm_eps)
        if cfg.is_moe:
            y, _ = moe_lib.moe_ffn(lp["moe"], h, cfg)
        else:
            y = mlp(lp["mlp"], h)
        return x + y, {"k": kvcache.fit_prefill(k, w), "v": kvcache.fit_prefill(v, w)}

    x, kvs = jax.lax.scan(body, x, params["layers"])
    cache = {"kv": kvs, "pos": jnp.asarray(s, jnp.int32)}
    if last_pos is not None:
        last = jnp.broadcast_to(jnp.asarray(last_pos, jnp.int32), (b,))
        x = x[jnp.arange(b), last][:, None]
        return _head(params, x, cfg), cache
    return _head(params, x[:, -1:], cfg), cache


def decode_step(params, cache, token, pos, cfg, *,
                attn_backend: str = "gather"):
    """token: (B,1) int32; pos: scalar int32 (tokens generated so far) for
    the lockstep paths, or a (B,) vector for the slot-table decode — each
    row then reads/writes its own cursor.

    A cache carrying a ``"ptab"`` page table (the serve engine's paged
    layout — see models/kvcache.py) switches the KV write/read to the
    block-table path: scatter through the table, attend over gathered
    pages. Math is identical to the dense path, so outputs are
    token-identical. An optional ``"wtab"`` write table redirects the KV
    SCATTER only (attention still gathers through ``ptab``) — the mixed
    token-slot step uses it to recompute positions whose KV already
    lives in shared prefix pages without rewriting pages other slots
    read (rows redirected to the null page 0). An optional ``"rows"``
    scalar is the live-row count: rows past it are pads, and the paged
    attention runs over the live rows only (layers.paged_attention's
    ``rows``). ``attn_backend`` picks the paged-attention execution path
    — ``"gather"`` (XLA gather + dense mask) or ``"pallas"`` (fused
    flash-decoding kernel); see layers.paged_attention.

    The parts of a layer run under ``jax.named_scope``s (``qkv``,
    ``kv_write``, ``attention`` inside layers.paged_attention,
    ``attn_out``, ``mlp``), the scan over them under ``layers`` (what
    it adds: each layer's weights and K/V sliced out of the stacks, the
    K/V stacked back), and the LM head under ``head``, so a profiler
    trace finds their device ops by a stable name. Metadata only: the
    numbers are those of the unscoped program.

    Returns (logits (B,1,V), new cache).
    """
    x = _embed(params, token, cfg)
    paged = "ptab" in cache
    w = cache["kv"]["k"].shape[2]
    ring = not paged and cfg.sliding_window > 0 and w == cfg.sliding_window
    pos = jnp.asarray(pos, jnp.int32)
    batched_pos = pos.ndim > 0
    positions = pos[:, None] if batched_pos else \
        jnp.full((token.shape[0], 1), pos)

    from repro.models.cp_attention import cp_available, cp_decode_attention
    use_cp = (cfg.cp_decode and not ring and not paged and not batched_pos
              and cp_available(cache["kv"]["k"][0]))

    def body(x, lp_kv):
        lp, kv = lp_kv
        with jax.named_scope("qkv"):
            h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
            q, k, v = attn_qkv(lp["attn"], h, cfg, positions=positions)
        if paged:
            with jax.named_scope("kv_write"):
                kv = kvcache.write_kv_paged(
                    kv, k, v, cache.get("wtab", cache["ptab"]),
                    positions[:, 0])
            ctx = paged_attention(q, kv["k"], kv["v"], cache["ptab"],
                                  positions[:, 0], backend=attn_backend,
                                  rows=cache.get("rows"))
        elif use_cp:
            # context-parallel: shard-local write + psum-softmax combine
            ctx, kv = cp_decode_attention(q, kv, k, v, pos,
                                          window=cfg.sliding_window)
        else:
            kv = kvcache.write_kv(kv, k, v, pos, ring=ring, window=w)
            kpos = kvcache.ring_kpos(positions, w) if ring else None
            kv_len = None if ring else jnp.minimum(pos + 1, w)
            ctx = attention(q, kv["k"], kv["v"], causal=True,
                            window=cfg.sliding_window, q_offset=pos,
                            kv_len=kv_len, kpos=kpos)
        with jax.named_scope("attn_out"):
            x = x + attn_out(lp["attn"], ctx, cfg)
        with jax.named_scope("mlp"):
            h = rmsnorm(x, lp["ln2"], cfg.norm_eps)
            if cfg.is_moe:
                y, _ = moe_lib.moe_ffn(lp["moe"], h, cfg)
            else:
                y = mlp(lp["mlp"], h)
            x = x + y
        return x, kv

    with jax.named_scope("layers"):
        x, kvs = jax.lax.scan(body, x, (params["layers"], cache["kv"]))
    out = {"kv": kvs, "pos": pos + 1}
    if paged:
        out["ptab"] = cache["ptab"]
    return _head(params, x, cfg), out
