"""Whisper-style encoder-decoder (audio). The mel+conv frontend is STUBBED:
``batch["frames"]`` carries precomputed (B, encoder_ctx, d_model) frame
embeddings (the assignment's one allowed stub). Sinusoidal positions,
bidirectional encoder, causal decoder with cross-attention, plain-GeLU MLPs
(as in Whisper).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.pspec import constrain
from repro.models import kvcache
from repro.models.layers import (attention, attn_out, attn_qkv, dense_init,
                                 init_attn, init_mlp, mlp, paged_attention,
                                 rmsnorm)


def sinusoid(length: int, dim: int):
    pos = np.arange(length)[:, None]
    i = np.arange(dim // 2)[None, :]
    ang = pos / np.power(10000.0, 2 * i / dim)
    return jnp.asarray(np.concatenate([np.sin(ang), np.cos(ang)], -1),
                       jnp.float32)


def init_enc_layer(key, cfg):
    ka, km = jax.random.split(key)
    return {"attn": init_attn(ka, cfg), "mlp": init_mlp(km, cfg, gated=False),
            "ln1": jnp.ones((cfg.d_model,), jnp.float32),
            "ln2": jnp.ones((cfg.d_model,), jnp.float32)}


def init_dec_layer(key, cfg):
    ka, kc, km = jax.random.split(key, 3)
    return {"attn": init_attn(ka, cfg), "xattn": init_attn(kc, cfg),
            "mlp": init_mlp(km, cfg, gated=False),
            "ln1": jnp.ones((cfg.d_model,), jnp.float32),
            "lnx": jnp.ones((cfg.d_model,), jnp.float32),
            "ln2": jnp.ones((cfg.d_model,), jnp.float32)}


def init(key, cfg):
    ke, kd, kt, kh = jax.random.split(key, 4)
    enc = jax.vmap(lambda k: init_enc_layer(k, cfg))(
        jax.random.split(ke, cfg.encoder_layers))
    dec = jax.vmap(lambda k: init_dec_layer(k, cfg))(
        jax.random.split(kd, cfg.num_layers))
    return {
        "tok_embed": dense_init(kt, (cfg.vocab_size, cfg.d_model),
                                jnp.dtype(cfg.dtype)),
        "enc_layers": enc, "dec_layers": dec,
        "enc_norm": jnp.ones((cfg.d_model,), jnp.float32),
        "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
        "lm_head": dense_init(kh, (cfg.d_model, cfg.vocab_size),
                              jnp.dtype(cfg.dtype)),
    }


def encode(params, frames, cfg, *, attn_impl="auto"):
    """frames: (B, enc_ctx, d_model) stub embeddings -> (B, enc_ctx, d)."""
    x = frames.astype(jnp.dtype(cfg.dtype))
    x = x + sinusoid(x.shape[1], cfg.d_model).astype(x.dtype)
    x = constrain(x, "batch", None, None)

    def body(x, lp):
        h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
        q, k, v = attn_qkv(lp["attn"], h, cfg, rope=False)
        ctx = attention(q, k, v, causal=False, impl=attn_impl)
        x = x + attn_out(lp["attn"], ctx, cfg)
        x = x + mlp(lp["mlp"], rmsnorm(x, lp["ln2"], cfg.norm_eps))
        return x, None

    x, _ = jax.lax.scan(body, x, params["enc_layers"])
    return rmsnorm(x, params["enc_norm"], cfg.norm_eps)


def _cross(lp, x, enc_kv, cfg):
    """Cross-attention with precomputed encoder K/V (B,T,Hkv,D)."""
    h = rmsnorm(x, lp["lnx"], cfg.norm_eps)
    b, s, _ = h.shape
    q = (h @ lp["xattn"]["wq"]).reshape(b, s, cfg.num_heads, cfg.head_dim)
    ctx = attention(q, enc_kv["k"], enc_kv["v"], causal=False, impl="full")
    return x + attn_out(lp["xattn"], ctx, cfg)


def cross_kv(lp, enc_out, cfg):
    b, t, _ = enc_out.shape
    k = (enc_out @ lp["xattn"]["wk"]).reshape(b, t, cfg.num_kv_heads,
                                              cfg.head_dim)
    v = (enc_out @ lp["xattn"]["wv"]).reshape(b, t, cfg.num_kv_heads,
                                              cfg.head_dim)
    return {"k": k, "v": v}


def forward(params, batch, cfg, *, remat: bool = False, attn_impl="auto"):
    """batch: {"tokens": (B,S), "frames": (B,enc_ctx,d)} -> dec logits."""
    enc_out = encode(params, batch["frames"], cfg, attn_impl=attn_impl)
    tokens = batch["tokens"]
    x = params["tok_embed"][tokens].astype(jnp.dtype(cfg.dtype))
    x = x + sinusoid(x.shape[1], cfg.d_model).astype(x.dtype)

    def body(x, lp):
        h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
        q, k, v = attn_qkv(lp["attn"], h, cfg, rope=False)
        ctx = attention(q, k, v, causal=True, impl=attn_impl)
        x = x + attn_out(lp["attn"], ctx, cfg)
        x = _cross(lp, x, cross_kv(lp, enc_out, cfg), cfg)
        x = x + mlp(lp["mlp"], rmsnorm(x, lp["ln2"], cfg.norm_eps))
        return x, None

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, _ = jax.lax.scan(body, x, params["dec_layers"])
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = constrain(x @ params["lm_head"], "batch", None, "vocab")
    return logits, jnp.zeros((), jnp.float32)


def init_cache(cfg, batch: int, max_len: int, dtype=None):
    dtype = dtype or jnp.dtype(cfg.dtype)
    kv = kvcache.init_kv(batch, max_len, cfg.num_kv_heads, cfg.head_dim,
                         dtype)
    xkv = kvcache.init_kv(batch, cfg.encoder_ctx, cfg.num_kv_heads,
                          cfg.head_dim, dtype)
    stack = lambda t: jax.tree.map(
        lambda a: jnp.zeros((cfg.num_layers,) + a.shape, a.dtype), t)
    return {"kv": stack(kv), "xkv": stack(xkv),
            "pos": jnp.zeros((), jnp.int32)}


def prefill(params, batch, cfg, cache, *, attn_impl="auto"):
    """Encode frames, precompute per-layer cross K/V, run prompt tokens."""
    enc_out = encode(params, batch["frames"], cfg, attn_impl=attn_impl)
    tokens = batch["tokens"]
    s = tokens.shape[1]
    w = cache["kv"]["k"].shape[2]
    x = params["tok_embed"][tokens].astype(jnp.dtype(cfg.dtype))
    x = x + sinusoid(s, cfg.d_model).astype(x.dtype)

    def body(x, lp):
        h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
        q, k, v = attn_qkv(lp["attn"], h, cfg, rope=False)
        ctx = attention(q, k, v, causal=True, impl=attn_impl)
        x = x + attn_out(lp["attn"], ctx, cfg)
        xkv = cross_kv(lp, enc_out, cfg)
        x = _cross(lp, x, xkv, cfg)
        x = x + mlp(lp["mlp"], rmsnorm(x, lp["ln2"], cfg.norm_eps))
        return x, ({"k": kvcache.fit_prefill(k, w), "v": kvcache.fit_prefill(v, w)}, xkv)

    x, (kvs, xkvs) = jax.lax.scan(body, x, params["dec_layers"])
    cache = {"kv": kvs, "xkv": xkvs, "pos": jnp.asarray(s, jnp.int32)}
    x = rmsnorm(x[:, -1:], params["final_norm"], cfg.norm_eps)
    return x @ params["lm_head"], cache


def decode_step(params, cache, token, pos, cfg, *,
                attn_backend: str = "gather"):
    """``pos``: scalar (lockstep) or (B,) per-row vector (slot-table).

    With a ``"ptab"`` page table in the cache (the serve engine's paged
    layout) the decoder self-attention KV goes through the block-table
    path; the cross-attention KV stays a dense per-slot block — its length
    is the FIXED encoder context, so paging it would buy nothing. An
    optional ``"wtab"`` write table redirects the KV scatter only (the
    mixed token-slot step's shared-prefix recompute path — see the dense
    transformer's decode_step), and an optional ``"rows"`` live-row
    count cuts the paged self-attention to the live rows (likewise).
    ``attn_backend`` picks the paged self-attention path: ``"gather"``
    or the fused ``"pallas"`` kernel (layers.paged_attention).
    """
    x = params["tok_embed"][token].astype(jnp.dtype(cfg.dtype))
    paged = "ptab" in cache
    w = (cache["ptab"].shape[1] * cache["kv"]["k"].shape[3] if paged
         else cache["kv"]["k"].shape[2])
    pos = jnp.asarray(pos, jnp.int32)
    pe_table = sinusoid(w, cfg.d_model)
    if pos.ndim:
        pe = pe_table[pos][:, None]                     # (B, 1, d)
    else:
        pe = jax.lax.dynamic_slice_in_dim(pe_table, pos, 1)
    x = x + pe.astype(x.dtype)
    positions = pos if pos.ndim else \
        jnp.full((token.shape[0],), pos, jnp.int32)

    def body(x, lp_kv):
        lp, kv, xkv = lp_kv
        h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
        q, k, v = attn_qkv(lp["attn"], h, cfg, rope=False)
        if paged:
            kv = kvcache.write_kv_paged(kv, k, v,
                                        cache.get("wtab", cache["ptab"]),
                                        positions)
            ctx = paged_attention(q, kv["k"], kv["v"], cache["ptab"],
                                  positions, backend=attn_backend,
                                  rows=cache.get("rows"))
        else:
            kv = kvcache.write_kv(kv, k, v, pos)
            ctx = attention(q, kv["k"], kv["v"], causal=True, q_offset=pos,
                            kv_len=jnp.minimum(pos + 1, w))
        x = x + attn_out(lp["attn"], ctx, cfg)
        x = _cross(lp, x, xkv, cfg)
        x = x + mlp(lp["mlp"], rmsnorm(x, lp["ln2"], cfg.norm_eps))
        return x, kv

    x, kvs = jax.lax.scan(body, x, (params["dec_layers"], cache["kv"],
                                    cache["xkv"]))
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = x @ params["lm_head"]
    out = {"kv": kvs, "xkv": cache["xkv"], "pos": pos + 1}
    if paged:
        out["ptab"] = cache["ptab"]
    return logits, out
