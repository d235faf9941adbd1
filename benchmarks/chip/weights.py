"""Seeded weights, made on the device in one jitted call, in the type
they are served in and in the layout the serve engine reads.

The benchmark makes the weights and hands them to the system under test;
the plain reference (reference.py) runs on the same arrays. Scales keep
every layer's contribution to the residual stream of order one, so the
attention over the paged cache moves the logits: with the small scales
of a training init the logits would hang on the current token alone and
a broken cache could pass the check.

  embed         (V, d)          N(0, 1); N(0, 1/d) where it is also the
                                LM head (tied), as lm_head's scale
  wq, wk, wv    (L, d, H*D)     N(0, 1/d)
  wo            (L, H*D, d)     N(0, 1/(H*D))
  w_gate, w_up  (L, d, F)       N(0, 1/d)
  w_down        (L, F, d)       N(0, 1/F)
  lm_head       (d, V)          N(0, 1/d)
  norm scales   float32         1 + N(0, 0.1^2)
"""
from __future__ import annotations

import numpy as np

from flops import Shape


def key_words(seed: int) -> np.ndarray:
    """Two 32-bit words from a seed of any size (``jax.random.key`` keeps
    only the low 32 bits of a larger one)."""
    return np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)


def layout(s: Shape, dtype):
    """``{path: (shape, dtype, std or None for a norm scale)}`` of the
    engine's parameter tree (models/transformer.py)."""
    import jax.numpy as jnp
    L, d, F, V = s.layers, s.d_model, s.d_ff, s.vocab
    q, kv = s.heads * s.head_dim, s.kv_heads * s.head_dim
    f32 = jnp.float32
    out = {
        ("embed",): ((V, d), dtype, d ** -0.5 if s.tied else 1.0),
        ("layers", "attn", "wq"): ((L, d, q), dtype, d ** -0.5),
        ("layers", "attn", "wk"): ((L, d, kv), dtype, d ** -0.5),
        ("layers", "attn", "wv"): ((L, d, kv), dtype, d ** -0.5),
        ("layers", "attn", "wo"): ((L, q, d), dtype, q ** -0.5),
        ("layers", "ln1"): ((L, d), f32, None),
        ("layers", "ln2"): ((L, d), f32, None),
        ("layers", "mlp", "w_gate"): ((L, d, F), dtype, d ** -0.5),
        ("layers", "mlp", "w_up"): ((L, d, F), dtype, d ** -0.5),
        ("layers", "mlp", "w_down"): ((L, F, d), dtype, F ** -0.5),
        ("final_norm",): ((d,), f32, None),
    }
    if s.qk_norm:
        out[("layers", "attn", "q_norm")] = ((L, s.head_dim), f32, None)
        out[("layers", "attn", "k_norm")] = ((L, s.head_dim), f32, None)
    if not s.tied:
        out[("lm_head",)] = ((d, V), dtype, d ** -0.5)
    return out


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = leaf
    return tree


def shapes(s: Shape, dtype) -> dict:
    """The parameter tree as ``jax.ShapeDtypeStruct`` leaves."""
    import jax
    return _nest({p: jax.ShapeDtypeStruct(shape, dt)
                  for p, (shape, dt, _) in layout(s, dtype).items()})


def make_params(s: Shape, dtype, seed: int, shardings=None):
    """Every parameter from ``seed`` in one jitted call; ``shardings`` (a
    tree like :func:`shapes`) places each leaf where the engine wants it,
    so nothing is copied or resharded afterwards."""
    import jax
    import jax.numpy as jnp

    spec = layout(s, jnp.dtype(dtype))

    def make(words):
        base = jax.random.wrap_key_data(words, impl="threefry2x32")
        flat = {}
        for i, (path, (shape, dt, std)) in enumerate(sorted(spec.items())):
            k = jax.random.fold_in(base, i)
            if std is None:
                x = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
            else:
                x = std * jax.random.normal(k, shape, jnp.float32)
            flat[path] = x.astype(dt)
        return _nest(flat)

    fn = jax.jit(make, out_shardings=shardings)
    return fn(jnp.asarray(key_words(seed)))
