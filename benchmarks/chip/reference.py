"""The plain reference that decides ``correct``, and its control.

A dense decoder (pre-norm RMSNorm, GQA with rotary positions on the
whole head, optional per-head q/k RMSNorm, SwiGLU MLP) written in plain
``jax.numpy`` with float32 activations and matmuls at ``highest``
precision. It imports nothing of the system under test: it reads the
benchmark's own weights (weights.py) and the tokens the client received.

Teacher-forced over ``prompt + served[:-1]``, the full forward (no
cache, no pages, no chunks, one sequence) gives the reference's logits
at every position whose next token was served. The number compared is
the widest gap by which a served token's reference logit lies below the
reference's best logit at that position. Greedy serving in the
configuration's precision puts the served token at or near the top; a
wrong token, a broken cache or a lost exchange between chips puts it
logits below.

The control is the same forward computed one precision step below the
configuration's bfloat16: every matmul's weight (per output channel) and
activation (per row) rounded to float8 e4m3, products summed in
float32. Its reading is the gap of the token IT puts first, taken from
the reference's logits at the same positions. The control must fail
the limit that sound runs of the program pass.
"""
from __future__ import annotations

from functools import partial

import numpy as np

#: the compare program pads every sequence to this many positions, so
#: one compiled program serves every request of a cell
PAD = 1024


def _fp8(x, axis):
    """Round ``x`` to float8 e4m3 with one scale per slice along
    ``axis`` (the reduction axis of the matmul it feeds)."""
    import jax.numpy as jnp
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


def _mm(x, w, low: bool):
    """``x @ w`` in float32 (highest); ``low`` rounds both operands to
    e4m3 first (activation per row, weight per output column)."""
    import jax.numpy as jnp
    w = w.astype(jnp.float32)
    if low:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.matmul(x, w, precision="highest")


def _rms(x, scale, eps):
    import jax.numpy as jnp
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) \
        * scale


def _rope(x, pos, theta):
    """Rotary positions on the whole head, halves rotated (x: S,H,D)."""
    import jax.numpy as jnp
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(freqs, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def logits_rows(params, tokens, rows, c, *, low: bool = False):
    """Float32 logits at positions ``rows`` of the sequence ``tokens``
    (S,), from the full causal forward. ``c``: the configuration file."""
    import jax
    import jax.numpy as jnp

    S = tokens.shape[0]
    H, Hkv, D = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    qk_norm = c.get("qk_norm", False)
    pos = jnp.arange(S)
    causal = pos[None, :] <= pos[:, None]

    def layer(x, lp):
        a = lp["attn"]
        h = _rms(x, lp["ln1"], eps)
        q = _mm(h, a["wq"], low).reshape(S, H, D)
        k = _mm(h, a["wk"], low).reshape(S, Hkv, D)
        v = _mm(h, a["wv"], low).reshape(S, Hkv, D)
        if qk_norm:
            q, k = _rms(q, a["q_norm"], eps), _rms(k, a["k_norm"], eps)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        k = jnp.repeat(k, H // Hkv, axis=1)      # query head h reads h//rep
        v = jnp.repeat(v, H // Hkv, axis=1)
        s = jnp.einsum("shd,thd->hst", q, k, precision="highest") / np.sqrt(D)
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        ctx = jnp.einsum("hst,thd->shd", p, v, precision="highest")
        x = x + _mm(ctx.reshape(S, H * D), a["wo"], low)
        h = _rms(x, lp["ln2"], eps)
        m = lp["mlp"]
        y = jax.nn.silu(_mm(h, m["w_gate"], low)) * _mm(h, m["w_up"], low)
        return x + _mm(y, m["w_down"], low), None

    x = params["embed"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = _rms(x[rows], params["final_norm"], eps)
    head = params["lm_head"] if "lm_head" in params else params["embed"].T
    return _head(x, head, low)


def _head(x, head, low: bool, block_bytes: int = 1 << 28):
    """``x @ head`` over vocabulary blocks of at most ``block_bytes`` in
    float32, so the upcast head never exists whole."""
    import jax
    import jax.numpy as jnp
    d, V = head.shape
    n = max(1, -(-d * V * 4 // block_bytes))
    while V % n:
        n += 1
    Vc = V // n
    out = jax.lax.map(
        lambda i: _mm(x, jax.lax.dynamic_slice_in_dim(head, i * Vc, Vc, 1),
                      low), jnp.arange(n))
    return out.transpose(1, 0, 2).reshape(x.shape[0], V)


@partial(__import__("jax").jit, static_argnames=("c_items", "control"))
def _compare(params, tokens, rows, served, c_items, control):
    import jax.numpy as jnp
    c = dict(c_items)
    ref = logits_rows(params, tokens, rows, c)
    top = ref.max(-1)
    gap = top - jnp.take_along_axis(ref, served[:, None], 1)[:, 0]
    if not control:
        return gap, gap
    low = logits_rows(params, tokens, rows, c, low=True).argmax(-1)
    gap_low = top - jnp.take_along_axis(ref, low[:, None], 1)[:, 0]
    return gap, gap_low


def gaps(params, c: dict, prompt, served, *, control: bool = False,
         pad: int = PAD):
    """Teacher-forced gaps of one request: for each served token, how far
    its reference logit lies below the reference's best (and, with
    ``control``, the same for the token the float8 control puts first).
    Returns ``(gap, gap_control or None)`` as float64 arrays."""
    import jax.numpy as jnp
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    seq = np.concatenate([prompt, served[:-1]])
    if len(seq) > pad:
        raise ValueError(f"sequence of {len(seq)} tokens > pad {pad}")
    tokens = np.zeros(pad, np.int32)
    tokens[:len(seq)] = seq
    n = len(served)
    rows = np.zeros(pad, np.int32)
    rows[:n] = np.arange(len(prompt) - 1, len(seq))
    tok = np.zeros(pad, np.int32)
    tok[:n] = served
    keep = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "rope_theta", "qk_norm")
    c_items = tuple(sorted((k, c[k]) for k in keep if k in c))
    g, gl = _compare(params, jnp.asarray(tokens), jnp.asarray(rows),
                     jnp.asarray(tok), c_items, bool(control))
    g = np.asarray(g, np.float64)[:n]
    gl = np.asarray(gl, np.float64)[:n] if control else None
    return g, gl
