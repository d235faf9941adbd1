"""Useful model FLOPs of a served step, from the model's shapes alone.

A row is one token a step computes. Useful rows are decode rows and
prefill-chunk rows; pad rows count for nothing. Each useful row costs

* 2 x the weights it multiplies in every layer (q, k, v, o projections
  and the gated MLP's three matrices), and
* causal attention at its own context length: a row at position ``p``
  attends ``p + 1`` keys, 2 FLOPs per multiply-add for q.k and again for
  p.v, in every query head of every layer,

and a row whose token is sampled also pays the LM head, 2 x d x vocab.
The embedding lookup is a gather and costs no FLOPs. So a program that
computes pad rows or the LM head for unsampled rows does work this file
does not count, and a share of the peak built on it cannot pass 100%.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Shape:
    """The sizes of a dense decoder with a gated (SwiGLU) MLP and GQA."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    qk_norm: bool = False
    tied: bool = False

    @classmethod
    def from_config(cls, c: dict) -> "Shape":
        """From a configuration file of ``configs/`` (HF key names)."""
        return cls(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"],
                   head_dim=c["head_dim"], d_ff=c["intermediate_size"],
                   vocab=c["vocab_size"], qk_norm=c.get("qk_norm", False),
                   tied=c.get("tie_word_embeddings", False))

    @property
    def layer_matmul_params(self) -> int:
        d, q, kv = self.d_model, self.heads * self.head_dim, \
            self.kv_heads * self.head_dim
        return d * q + 2 * d * kv + q * d + 3 * d * self.d_ff


def param_count(s: Shape) -> int:
    """Every parameter of the model: embedding, LM head (untied), the
    layers' matrices and norm scales (qk norms included) and the final
    norm."""
    per_layer = s.layer_matmul_params + 2 * s.d_model \
        + (2 * s.head_dim if s.qk_norm else 0)
    head = 0 if s.tied else s.vocab * s.d_model
    return s.vocab * s.d_model + head + s.layers * per_layer + s.d_model


def row_flops(s: Shape, position: int, sampled: bool) -> float:
    """FLOPs of one useful row at context position ``position``."""
    f = 2.0 * s.layers * s.layer_matmul_params
    f += 4.0 * s.layers * s.heads * s.head_dim * (position + 1)
    if sampled:
        f += 2.0 * s.d_model * s.vocab
    return f


def step_flops(s: Shape, rows) -> float:
    """FLOPs of a step's useful rows, each ``(position, sampled)``."""
    return sum(row_flops(s, p, smp) for p, smp in rows)
