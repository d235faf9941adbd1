"""Inter-operator (pipeline) parallelism — paper §3.2/§4, Fig. 5.

TPU-idiomatic implementation: stages live on a dedicated "pipe" mesh axis;
activations move stage-to-stage with ``jax.lax.ppermute`` inside
``shard_map`` (the ICI-neighbour equivalent of PipeDream's P2P sends), and
micro-batches stream through a GPipe schedule expressed as a ``lax.scan``
over T = M + P - 1 ticks (Fig. 5c/5d exactly: the first P-1 and last P-1
ticks are the bubble).

The module also provides the schedule SIMULATOR used by
benchmarks/bench_pipeline_bubble.py to reproduce the paper's bubble-fraction
claims for GPipe and 1F1B without hardware.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# ------------------------------------------------------------ runtime (JAX)

def gpipe_spmd(stage_fn: Callable, microbatches, *, axis: str = "pipe"):
    """Run inside shard_map. ``stage_fn(x) -> y`` applies THIS device's
    stage; ``microbatches``: (M, mb, ...) replicated along ``axis``.

    Returns (M, mb, ...) final-stage outputs (replicated along ``axis``).
    Every stage computes every tick; ticks where a stage holds no valid
    micro-batch are the pipeline bubble (wasted FLOPs, exactly GPipe).
    """
    p = jax.lax.psum(1, axis)
    idx = jax.lax.axis_index(axis)
    m = microbatches.shape[0]
    t_total = m + p - 1

    def tick(carry, t):
        buf, outs = carry
        x0 = microbatches[jnp.clip(t, 0, m - 1)]
        x = jnp.where(idx == 0, x0, buf)
        y = stage_fn(x)
        out_i = jnp.clip(t - (p - 1), 0, m - 1)
        write = jnp.logical_and(idx == p - 1, t >= p - 1)
        upd = jax.lax.dynamic_update_index_in_dim(outs, y, out_i, 0)
        outs = jnp.where(write, upd, outs)
        # stage i -> i+1 ring (last stage's send is ignored by stage 0)
        buf = jax.lax.ppermute(y, axis,
                               [(i, (i + 1) % p) for i in range(p)])
        return (buf, outs), None

    buf0 = jnp.zeros_like(microbatches[0])
    outs0 = jnp.zeros_like(microbatches)
    (_, outs), _ = jax.lax.scan(tick, (buf0, outs0), jnp.arange(t_total))
    # replicate the last stage's outputs to every stage member
    outs = jax.lax.psum(jnp.where(idx == p - 1, outs, jnp.zeros_like(outs)),
                        axis)
    return outs


def pipeline_apply(stage_fn: Callable, stage_params, x, *, mesh: Mesh,
                   num_microbatches: int, axis: str = "pipe"):
    """High-level entry: ``stage_params`` leaves have leading dim P (one
    slice per stage, sharded over ``axis``); ``x``: (B, ...) global batch.

    stage_fn(params_slice, x_mb) -> y_mb with y_mb.shape == x_mb.shape.
    """
    b = x.shape[0]
    assert b % num_microbatches == 0
    micro = x.reshape(num_microbatches, b // num_microbatches, *x.shape[1:])

    pspec = P(axis)  # leading stage dim
    in_specs = (
        jax.tree.map(lambda _: pspec, stage_params),
        P(*([None] * micro.ndim)),
    )

    def spmd(params, mb):
        local = jax.tree.map(lambda a: a[0], params)  # strip stage dim
        return gpipe_spmd(lambda xx: stage_fn(local, xx), mb, axis=axis)

    out = jax.shard_map(spmd, mesh=mesh, in_specs=in_specs,
                    out_specs=P(*([None] * micro.ndim)),
                    check_vma=False)(stage_params, micro)
    return out.reshape(b, *x.shape[1:])


# ------------------------------------------------------- schedule simulator

def simulate_schedule(num_stages: int, num_micro: int, *,
                      schedule: str = "gpipe",
                      fwd_time: float = 1.0,
                      bwd_time: float = 2.0) -> dict:
    """Tick-level simulation of GPipe vs 1F1B (Fig. 5c/5d + PipeDream [20]).

    Returns total time, ideal time, bubble fraction, and peak in-flight
    micro-batches per stage (the activation-memory driver [14]).
    """
    p, m = num_stages, num_micro
    if schedule == "gpipe":
        total = (m + p - 1) * fwd_time + (m + p - 1) * bwd_time
        ideal = m * (fwd_time + bwd_time)
        in_flight = min(m, p) if m else 0
        in_flight = m  # GPipe stores all micro-batch activations
    elif schedule == "1f1b":
        # warmup p-1 fwd, steady 1F1B, drain p-1 bwd
        total = (p - 1) * fwd_time + m * (fwd_time + bwd_time) \
            + (p - 1) * bwd_time
        ideal = m * (fwd_time + bwd_time)
        in_flight = min(m, p)
    else:
        raise ValueError(schedule)
    bubble = 1.0 - ideal / total
    # closed-form check from the paper: (p-1)/(m+p-1) for equal fwd/bwd split
    closed_form = (p - 1) / (m + p - 1)
    return {"schedule": schedule, "stages": p, "microbatches": m,
            "total_time": total, "ideal_time": ideal,
            "bubble_fraction": bubble, "closed_form_gpipe": closed_form,
            "peak_inflight_microbatches": in_flight}
