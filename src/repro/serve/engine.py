"""Serving engine: continuous batching over ONE batched decode step.

A fixed-size slot table (the batch) holds independent requests at
different generation depths. The whole table advances with a SINGLE
jitted decode call per engine step: positions are a per-slot vector, and
``decode_step`` scatters each row's new KV at its own cursor while the
attention mask keeps each row inside its own valid prefix. Finished/empty
slots are masked on device — their sampled tokens are zeroed and their
cursors frozen — so device dispatch per step is O(1) in the number of
active slots.

KV layouts (models/kvcache.py):

  * PAGED (default where supported — vLLM-style block tables): one flat
    pool of ``page_size``-token pages shared by every slot, plus a
    per-slot page table. Admission reserves pages from a host-side
    refcounted free-list (serve/paging.py) and releases them when the
    request retires, so a short request holds pages for ITS context, not
    a dense ``max_len`` row. The page table is a device array whose
    VALUES change at admission/retire while its shape never does, so the
    whole run still traces exactly one decode program.
  * DENSE (``paged=False``, and the automatic fallback): one contiguous
    ``max_len`` (or ring-window) row per slot. Sliding-window (ring) and
    SSM/hybrid archs keep this layout — a ring cache is already O(window)
    and the SSM state is O(1), so pages would add indirection for no
    memory win.

On the paged layout three independent features stack (all off by
default, preserving the PR 3 worst-case-reservation behaviour):

  * ``prefix_cache=True`` — a radix tree over page-aligned token blocks
    (serve/prefix.py) maps shared prompt prefixes to refcounted pool
    pages, so N requests with a common system prompt hold ONE physical
    copy. Exact for dense decoders (causal KV depends only on the
    prefix); enc-dec keys additionally on a digest of the request's
    frames, and MoE on a digest of the full context (capacity routing
    makes block KV portable only between identical contexts). Registered
    pages stay resident after their owner retires (cheap re-prefill for
    repeat prompts and preempted victims) and are evicted LRU-first
    under pool pressure.
  * ``lazy=True`` — admission reserves only ``ceil((len(prompt +
    emitted) + 1) / page_size)`` pages — the prompt plus its first
    decode write, one page beyond the prompt's only when it ends on a
    page boundary — instead of the worst-case
    ``ceil((prompt + max_new - 1) / page_size)``;
    ``step`` grows the reservation when a slot's cursor crosses a page
    boundary. The pool can now run dry MID-DECODE: the engine then
    evicts cold prefix pages and, if still short, PREEMPTS the
    least-progress slot (serve/scheduler.py) — the victim's private
    pages are freed (prefix pages merely drop a reference), and the
    request is requeued at the FIFO head with its partial output; its
    re-prefill over prompt+output resumes decoding exactly (greedy
    decode is bit-identical to the uninterrupted run). Lazy mode also
    unlocks partial-tail prefix hits, whose adopted page is duplicated
    by COPY-ON-WRITE (``allocator.cow`` + ``kvcache.copy_page``) before
    the slot's first decode write lands in it.
  * ``scheduler=`` — the admission/preemption policy object; the default
    ``FifoLeastProgress`` keeps FIFO head-of-line admission and preempts
    the fewest-generated-tokens slot first.

All of it is host-side bookkeeping plus page-table VALUES — prefill and
decode stay exactly one trace each, sharing or not (asserted by the CI
paged-serve smoke and tests/test_serve_prefix.py).

MIXED token-slot stepping (default on the paged layout, PR 7): instead
of the two-program prefill/decode split, each step runs ONE program over
a ``chunk_tokens``-token batch — every decoding slot's next token first,
then prefill CHUNKS of admitted-but-unprefilled requests
(sglang/vLLM-style chunked prefill). Admission only reserves pages and
enqueues the prefill work; the step loop drains it cursor-by-cursor
through the paged KV scatter, sampling a request's first token on the
chunk containing its final prompt position. A long prompt therefore
never stalls decoding slots — it shares each step's budget with them
(TTFT p99 under mixed workloads is the win the bench's
``--mixed-workload`` mode measures). Chunking is EXACT: the program
scatters every chunk token's K/V before the attention gathers, so greedy
output is bit-identical to the legacy split path (test-pinned for dense,
MoE and enc-dec, tp2/dp2 included), and the batch is statically
``chunk_tokens`` wide, so the program retraces once per page bucket —
trace count stays bounded. ``mixed=False`` keeps the legacy split path
on the paged layout; dense-layout archs (SWA ring, SSM/hybrid) always
use it.

Legacy admission fills free slots from a FIFO queue between steps (the
standard orca/vllm outer loop). Prefill pads prompts to power-of-two
buckets (serve/step.prefill_bucket) so XLA retraces at most
log2(max_len) prefill shapes; paged prefill additionally rounds the
bucket up to whole pages and scatters the fresh KV page-wise
(kvcache.scatter_prefill_pages), skipping blocks the prefix cache
already holds. Sampling (greedy or temperature) runs on device inside
the same jitted step (serve/sampling.py).

Caveats: MoE archs skip prompt bucketing, and their batched decode can
differ from single-request decode — capacity-based expert routing couples
rows of a batch (pad/neighbour tokens consume expert capacity). Dense,
SSM and hybrid archs are row-independent and token-identical to
sequential decoding. Enc-dec (audio) requests must carry precomputed
frame embeddings (``submit(..., frames=...)`` — the mel+conv frontend is
the assignment's allowed stub); their decoder KV pages like any dense
decoder while the cross-attention KV stays one fixed-size block per slot.
Preemption keeps greedy outputs bit-identical for row-independent archs
(resume-by-re-prefill recomputes exactly the KV the victim held); MoE
extends its standing caveat — a re-prefill routes the whole context under
prefill capacity, where the uninterrupted run would have routed the tail
token-by-token — and with ``temperature > 0`` a preempted request resumes
on a different rng draw (stochastic either way).

Intra-operator (TP) sharded serving (``mesh=``)
-----------------------------------------------
Passing a ("data", "model") mesh (+ the Strategy whose rules map logical
axes onto it) runs the SAME one-trace prefill/decode programs sharded
GSPMD-style across the mesh's ``tp`` devices: params take the Megatron
§5.1 layout (core/sharding.param_pspecs), and the paged pool keeps its
flat ``(L, n_pages, Hkv, page_size, D)`` shape but is HEAD-SHARDED over
"model" — each device holds ``Hkv/tp`` heads of every page, so resident
per-device KV is ~1/tp of the unsharded pool while the page axis stays
whole (the block-table gather indexes it). The page table, cursors and
sampled logits are replicated; admission/retire still only rewrites
table VALUES, so the one-decode-trace invariant survives sharding
(tests/test_serve_parallel.py pins tp=2 token parity vs tp=1). Data
parallelism is one level up: ``serve/parallel.ReplicaRouter``
instantiates ``dp`` engine replicas over disjoint device slices and
routes requests between them.

Decode cost tracks OCCUPANCY, not capacity: the page table handed to the
decode program is clipped to the power-of-two bucket of the live page
high-water mark — the allocator's per-owner peak, with every admission's
worst-case reservation pre-booked so lazy growth never re-buckets
mid-decode (serve/step.page_bucket, ``_sync_ptab``). The paged-attention
gather then reads ``bucket * page_size`` positions per row instead of
the full ``max_len`` table width, and the program retraces only when an
admission pushes the high-water across a bucket boundary. Cost tracks
the LIVE rows too: the mixed step's pads (every row of the budget past
the last live one) skip the attention, which runs over the smallest
width of a static ladder (8, 16, ..., ``chunk_tokens``) holding the
live rows — one ``lax.switch`` branch per width inside the same
program, so no width adds a trace (layers.paged_attention's ``rows``;
``stats["attn_steps_w<width>"]`` counts the steps each width ran). The
pads' matmuls and LM head still run over the whole budget.

``engine.stats`` counts device calls AND traces (``decode_traces`` /
``prefill_traces`` increment only while tracing), so tests can assert the
one-program property directly — plus pool telemetry: ``pages_in_use`` /
``peak_pages``, prefix-cache ``prefix_hit_blocks`` /
``prefix_miss_blocks`` / ``prefix_tail_hits`` / ``prefix_evictions``,
``preemptions`` and ``cow_copies``.

Preferred construction: ``repro.api.Session.serve(slots=..., max_len=...,
page_size=..., prefix_cache=..., lazy=...)`` — the Session supplies the
params so callers never thread param trees by hand, and its ``plan=`` /
``tp=`` / ``dp=`` arguments pick sharded/replicated serving.
"""
from __future__ import annotations

import hashlib
import time
from collections import deque
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import sharding as shd
from repro.core.pspec import sharding_rules
from repro.core.strategy import Strategy
from repro.models import get_model, kvcache
from repro.models.layers import row_width, row_widths
from repro.serve.paging import PageAllocator, pages_for
from repro.serve.prefix import PrefixCache
from repro.serve.sampling import sample_tokens
from repro.serve.scheduler import FifoLeastProgress
from repro.serve.step import pack_token_budget, page_bucket, prefill_bucket
from repro.serve.tracing import NULL_STEP, Tracer, chrome_trace, \
    export_chrome_trace

#: archs the token-only engine can serve without per-request extras.
TOKEN_ONLY_ARCHS = ("dense", "moe", "ssm", "hybrid")
#: + enc-dec audio (stubbed frame embeddings) and VLM (stubbed image
#: patch embeddings) — each request carries its modality tensor.
SERVABLE_ARCHS = TOKEN_ONLY_ARCHS + ("audio", "vlm")
#: archs whose decode cache can use the paged (block-table) layout.
PAGEABLE_ARCHS = ("dense", "moe", "audio")


@dataclass
class Request:
    """One request's lifecycle record; ``run()`` returns these so callers
    can distinguish completion (``done=True``) from truncation by
    ``max_steps`` (``done=False`` with partial/empty ``out``). A preempted
    request keeps its partial ``out`` while requeued — re-admission
    prefills over prompt+out and resumes. A request whose ``deadline``
    passes while still QUEUED finishes ``done=False, expired=True``
    instead of occupying the scheduler's head."""
    rid: int
    prompt: np.ndarray                 # (len,) int32
    max_new: int
    out: List[int] = field(default_factory=list)
    done: bool = False
    frames: Optional[np.ndarray] = None   # (enc_ctx, d_model), audio archs
    images: Optional[np.ndarray] = None   # (n_img_tok, d_model), vlm archs
    priority: int = 0                  # scheduler hint (serve/scheduler.py)
    deadline: Optional[float] = None   # absolute time.monotonic() SLO bound
    expired: bool = False              # deadline passed while queued
    # host timestamp of the FIRST generated token (set at prefill
    # completion, so TTFT covers requests that finish at admission)
    first_tok_t: Optional[float] = field(default=None, repr=False)
    # memoized (ctx_len, salt) — a backpressured head-of-line request
    # re-places every step and must not re-hash its frames/context
    salt_cache: Optional[tuple] = field(default=None, repr=False)


class ServeEngine:
    def __init__(self, cfg, params, *, slots: int = 4, max_len: int = 256,
                 eos_id: Optional[int] = None, temperature: float = 0.0,
                 seed: int = 0, paged: Optional[bool] = None,
                 page_size: int = 16, kv_pages: Optional[int] = None,
                 prefix_cache: bool = False, lazy: bool = False,
                 scheduler=None, mesh=None, strategy=None,
                 mixed: Optional[bool] = None, chunk_tokens: int = 256,
                 attn_backend: str = "gather", spec=None,
                 tracer=None, trace_level: int = 1):
        if cfg.arch_type not in SERVABLE_ARCHS:
            raise ValueError(
                f"{cfg.name}: the engine drives token/frame decoders "
                f"({'/'.join(SERVABLE_ARCHS)}), not {cfg.arch_type}")
        pageable = (cfg.arch_type in PAGEABLE_ARCHS
                    and cfg.sliding_window == 0)
        if (prefix_cache or lazy) and not pageable:
            raise ValueError(
                f"{cfg.name}: prefix_cache/lazy ride on the paged KV "
                f"pool, which needs a full-attention decoder "
                f"({'/'.join(PAGEABLE_ARCHS)}, no sliding window) — "
                f"unavailable for {cfg.arch_type}"
                + (" + SWA ring" if cfg.sliding_window else ""))
        if paged is None:
            # auto: paged for every full-attention decoder. Exact vs dense
            # for row-independent archs; MoE keeps its standing batched-
            # routing caveat (see module docstring) under either layout.
            # prefix_cache/lazy are paged-pool features, so requesting
            # them resolves auto to paged.
            paged = True if (prefix_cache or lazy) else pageable
        if paged and not pageable:
            raise ValueError(
                f"{cfg.name}: paged KV needs a full-attention decoder "
                f"({'/'.join(PAGEABLE_ARCHS)}, no sliding window); "
                f"{cfg.arch_type}"
                + (" + SWA ring" if cfg.sliding_window else "")
                + " keeps the dense layout (paged=False)")
        if (prefix_cache or lazy) and not paged:
            raise ValueError(
                f"{cfg.name}: prefix_cache/lazy ride on the paged pool; "
                "drop paged=False to use them")
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        # -------- mixed token-slot stepping (chunked prefill, PR 7):
        # ONE program per step processes every active slot's decode token
        # plus prefill CHUNKS of admitted-but-unprefilled requests inside
        # a bounded token budget. Default wherever the paged layout is —
        # chunking rides on the page-table scatter/gather; dense layouts
        # keep the legacy two-program split.
        if mixed is None:
            mixed = bool(paged)
        if mixed and not paged:
            raise ValueError(
                f"{cfg.name}: the mixed token-slot step writes prefill "
                "chunks through the paged KV scatter; drop paged=False "
                "(or pass mixed=False) to serve this arch")
        if mixed and chunk_tokens < max(slots, 1):
            raise ValueError(
                f"chunk_tokens ({chunk_tokens}) must be >= slots "
                f"({slots}): every active slot's decode token is "
                "reserved in the budget before any prefill chunk")
        self.mixed = bool(mixed)
        self.chunk_tokens = int(chunk_tokens)
        # -------- paged-attention decode backend (PR 8): "gather" keeps
        # the XLA gather + dense-mask path; "pallas" runs the fused
        # flash-decoding kernel (kernels/paged_attention.py — interpret
        # mode on CPU). Token-identical greedy outputs, same one-trace-
        # per-bucket cadence; the kernel only exists for the paged pool.
        if attn_backend not in ("gather", "pallas"):
            raise ValueError(
                f"attn_backend must be 'gather' or 'pallas', "
                f"got {attn_backend!r}")
        if attn_backend == "pallas" and not paged:
            raise ValueError(
                f"{cfg.name}: attn_backend='pallas' is the fused paged-"
                "attention decode kernel — it needs the paged KV layout "
                "(drop paged=False)")
        self.attn_backend = attn_backend
        # only the paged decoders (transformer/encdec decode_step) take
        # the kwarg; the default backend stays a clean positional call so
        # ssm/hybrid decode paths are untouched
        self._attn_kw = {} if attn_backend == "gather" \
            else {"attn_backend": attn_backend}
        # -------- speculative multi-token decode (PR 9): a SpecConfig
        # turns each decoding slot's one row into 1 + k rows of the SAME
        # mixed program — drafted tokens at consecutive positions,
        # verified in one dispatch, longest greedy-matching prefix
        # accepted (+1 bonus). serve/speculative.py holds the drafters.
        self.spec = spec
        self._drafter = None
        if spec is not None:
            if not mixed:
                raise ValueError(
                    f"{cfg.name}: speculative decode packs draft rows "
                    "into the mixed token-slot step; it needs the paged "
                    "layout with mixed=True (the default there)")
            if temperature > 0.0:
                raise ValueError(
                    "speculative decode is greedy-only (temperature "
                    f"0.0, got {temperature}): acceptance compares the "
                    "verifier's argmax tokens — stochastic speculative "
                    "sampling is a different acceptance rule")
            if chunk_tokens < max(slots, 1) * (spec.k + 1):
                raise ValueError(
                    f"chunk_tokens ({chunk_tokens}) must be >= slots * "
                    f"(spec.k + 1) = {slots * (spec.k + 1)}: every "
                    "slot's base decode row plus its k draft rows is "
                    "reserved in the budget before any prefill chunk")
            from repro.serve.speculative import make_drafter
            self._drafter = make_drafter(spec, cfg, max_len=max_len,
                                         seed=seed)
        # -------- intra-operator (TP) sharding: mesh + logical-axis rules
        self.mesh = mesh
        self.tp = int(mesh.shape.get("model", 1)) if mesh is not None else 1
        if mesh is not None:
            self.strategy = strategy if strategy is not None \
                else Strategy(dtype=cfg.dtype)
            self._rules = self.strategy.rules(mesh)
            # Megatron param layout on the engine's mesh (a no-op when the
            # caller already sharded them there)
            params = jax.device_put(
                params, shd.param_shardings(params, self.strategy, mesh))
            self._ctx = lambda: sharding_rules(self.mesh, self._rules)
            self._placement = NamedSharding(mesh, P())
        else:
            self.strategy = strategy
            self._ctx = nullcontext
            # unsharded, but the params may sit on a one-device mesh (a
            # Session's): keep the decode state and host inputs on that
            # same mesh, or the first step's outputs come back with a
            # sharding its inputs lacked and the step compiles twice
            home = getattr(jax.tree.leaves(params)[0], "sharding", None)
            self._placement = NamedSharding(home.mesh, P()) \
                if isinstance(home, NamedSharding) else None
        self.cfg, self.params = cfg, params
        self.model = get_model(cfg)
        self.slots = slots
        self.max_len = max_len
        self.eos_id = eos_id
        self.temperature = temperature
        self.paged = paged
        self.page_size = page_size
        self.lazy = lazy
        self.prefix_cache = prefix_cache
        # FIFO admission queue: deque so heavy-traffic admission stays O(1)
        # per pop (a list's pop(0) is O(n) in queued requests)
        self.queue: Deque[Request] = deque()
        self.active: List[Optional[Request]] = [None] * slots
        self.finished: Dict[int, Request] = {}
        self.stats = {"decode_steps": 0, "decode_traces": 0,
                      "prefills": 0, "prefill_traces": 0,
                      "pages_in_use": 0, "peak_pages": 0,
                      "prefix_hit_blocks": 0, "prefix_miss_blocks": 0,
                      "prefix_tail_hits": 0, "prefix_evictions": 0,
                      "preemptions": 0, "cow_copies": 0,
                      # online telemetry (PR 6): step_count counts every
                      # step() call (decode_steps only the ones that ran
                      # the device program), decode_tokens every token
                      # generated (prefill-sampled ones included),
                      # wall_time_s the host seconds spent inside step(),
                      # tokens_per_s_ewma a smoothed generation rate —
                      # the DP router's routing signal —, and
                      # prefix_decode_blocks the page-aligned blocks
                      # registered from DECODE output (prompt blocks are
                      # counted by the prefix hit/miss pair)
                      "step_count": 0, "decode_tokens": 0,
                      "wall_time_s": 0.0, "tokens_per_s_ewma": 0.0,
                      "prefix_decode_blocks": 0,
                      # mixed-step telemetry (PR 7): prefill tokens
                      # processed as chunks, deadline-expired queued
                      # requests, audio encoder traces (the mixed path
                      # runs the encoder as its own small program)
                      "prefill_chunk_tokens": 0, "expired": 0,
                      "encode_traces": 0,
                      # speculative decode (PR 9): drafted tokens packed
                      # as verify rows, and how many the verifier
                      # accepted (bonus tokens are ordinary decode
                      # tokens, not counted here) — accept rate is
                      # spec_accepted / spec_drafted;
                      # decode_slot_steps counts (step, decoding slot)
                      # pairs — the honest denominator for tokens-per-
                      # step: (decode_tokens - prefills) over it is
                      # exactly 1.0 without speculation and in
                      # [1, k + 1] with it, slot count notwithstanding
                      "spec_drafted": 0, "spec_accepted": 0,
                      "decode_slot_steps": 0,
                      # which paged-attention path the decode program
                      # runs (PR 8); a string — metrics render it as a
                      # labeled serve_engine_decode_backend info gauge
                      "decode_backend": attn_backend}
        # -------- observability (PR 10, serve/tracing.py): request span
        # trees + per-step phase records + flight-recorder rings. Always
        # present — trace_level=0 turns every hook into an O(1) no-op;
        # the default level keeps lifecycle events and step records,
        # level 2 adds per-chunk detail to the request trees. A DP
        # router stamps each replica's ``tracer.replica`` after
        # construction so merged exports get distinct lanes.
        self.tracer = tracer if tracer is not None \
            else Tracer(level=trace_level)
        self._rng = jax.random.key(seed)
        self._sched = scheduler if scheduler is not None \
            else FifoLeastProgress()
        # the slot table: one batched cache, per-slot position vector
        self._cache = self.model.init_cache(cfg, slots, max_len)
        self._cache["pos"] = jnp.zeros((slots,), jnp.int32)
        self._pos = np.zeros(slots, np.int64)    # host mirror: tokens in ctx
        self._last = np.zeros(slots, np.int64)   # host mirror: last token
        self._prefix: Optional[PrefixCache] = None
        if paged:
            # swap the dense per-slot rows for a flat page pool + table;
            # page 0 is the null page (inactive-slot / padding scratch)
            pps = pages_for(max_len, page_size)  # table width: blocks/slot
            self.kv_pages = kv_pages if kv_pages is not None \
                else slots * pps
            if self.kv_pages < 1:
                raise ValueError(
                    f"kv_pages must be >= 1, got {self.kv_pages}")
            dtype = self._cache["kv"]["k"].dtype
            self._cache["kv"] = kvcache.init_paged_kv(
                cfg.num_layers, self.kv_pages + 1, page_size,
                cfg.num_kv_heads, cfg.head_dim, dtype)
            # the DEVICE page table is clipped to the power-of-two bucket
            # of the allocator's per-slot page high-water mark (_sync_ptab)
            # so the decode gather reads occupancy, not max_len; the host
            # mirror stays full-width
            self._pps = pps
            self._gather = 1
            self._hw_blocks = 1
            self._cache["ptab"] = jnp.zeros((slots, self._gather), jnp.int32)
            self._ptab = np.zeros((slots, pps), np.int64)
            self._ptab_dirty = False
            self._alloc = PageAllocator(self.kv_pages, page_size,
                                        first_page=1)
            if prefix_cache:
                self._prefix = PrefixCache(self._alloc, page_size)
            self._copy_page = jax.jit(kvcache.copy_page,
                                      donate_argnums=(0,))
        if mesh is not None:
            # place the decode state onto the mesh: pool head-sharded over
            # "model", dense leaves per the usual cache rules, page table /
            # cursors replicated (core/sharding.cache_pspecs)
            self._cache = jax.device_put(
                self._cache,
                jax.tree.map(lambda s: NamedSharding(mesh, s),
                             shd.cache_pspecs(self._cache, self.strategy,
                                              mesh, slots)))
        elif self._placement is not None:
            self._cache = jax.device_put(self._cache, self._placement)
        # bucketing: attention masks make right-padding exact for dense;
        # MoE capacity routing and the SSM recurrence are perturbed by pad
        # tokens (and enc-dec prefill gathers no last_pos), so those archs
        # prefill at exact length (retrace per len).
        self._bucketed = cfg.arch_type == "dense"
        self._window = max_len if paged else \
            (self._cache["kv"]["k"].shape[2]
             if "kv" in self._cache else max_len)
        self._decode = jax.jit(self._decode_fn, donate_argnums=(1,))
        self._prefill = jax.jit(self._prefill_fn, donate_argnums=(1,))
        # mid-prefill slot table (mixed step): slot -> {ctx, n, cursor,
        # covered, dep, salt, seq}. Always present so the legacy path's
        # _grow_and_cow/_preempt can check membership unconditionally.
        self._pf: Dict[int, dict] = {}
        self._admit_seq = 0
        # chunk-boundary cancellation hook: the AsyncDriver points this
        # at its ``abort_step`` Event; a set flag makes step() return
        # before launching the next program (watchdog recovery then runs
        # at sub-step latency instead of waiting out a full step)
        self.abort_event = None
        if self.mixed:
            self._mixed = jax.jit(self._mixed_fn, donate_argnums=(1,))
            # steps whose paged attention ran at each width of the
            # live-row ladder (layers.row_widths)
            for w in row_widths(self.chunk_tokens):
                self.stats[f"attn_steps_w{w}"] = 0
            if cfg.arch_type == "audio":
                self._encode = jax.jit(self._encode_fn,
                                       donate_argnums=(1,))

    # ------------------------------------------------------------ memory
    def kv_bytes(self) -> int:
        """GLOBAL device bytes RESIDENT in the engine's decode state (KV
        pool/rows, SSM states, cross-attention blocks; cursors and the
        page table are negligible and excluded), summed over the mesh
        when sharded. Static for the engine's lifetime — the paged pool
        is allocated up front. Step TRANSIENTS are extra: paged decode
        gathers each slot's BUCKETED table width per layer (the
        occupancy high-water bound, see layers.paged_attention), so the
        per-step scratch tracks live pages while this number is what
        lives in HBM between steps."""
        return sum(leaf.size * leaf.dtype.itemsize
                   for key, big in self._cache.items()
                   if key not in ("pos", "ptab")
                   for leaf in jax.tree.leaves(big))

    def per_device_kv_bytes(self) -> int:
        """Resident decode-state bytes on ONE device: the head-sharded
        pool puts ~1/tp of :meth:`kv_bytes` on each of the mesh's
        devices (exactly 1/tp when every leaf's kv-head axis divides);
        equals :meth:`kv_bytes` unsharded."""
        total = 0
        for key, big in self._cache.items():
            if key in ("pos", "ptab"):
                continue
            for leaf in jax.tree.leaves(big):
                sharding = getattr(leaf, "sharding", None)
                if sharding is not None:
                    shape = sharding.shard_shape(leaf.shape)
                    total += int(np.prod(shape)) * leaf.dtype.itemsize
                else:
                    total += leaf.size * leaf.dtype.itemsize
        return total

    # --------------------------------------------------- device plumbing
    def _dev(self, x):
        """Put a host value on the engine's device(s) (replicated across
        the mesh when sharded) so jit sees one stable input sharding —
        uncommitted host arrays would leave the placement choice to the
        compiler."""
        if self._placement is None:
            return jnp.asarray(x)
        return jax.device_put(x, self._placement)

    # ------------------------------------------------------- jitted steps
    def _decode_fn(self, params, cache, tokens, pos, active, rng):
        """ONE device program advancing every slot: batched decode +
        on-device sampling + active-slot masking."""
        self.stats["decode_traces"] += 1     # Python side effect: trace-time only
        logits, cache = self.model.decode_step(params, cache, tokens, pos,
                                               self.cfg, **self._attn_kw)
        tok = sample_tokens(logits[:, -1], rng=rng,
                            temperature=self.temperature)
        tok = jnp.where(active, tok, 0)
        cache["pos"] = jnp.where(active, pos + 1, pos)
        return tok, cache

    def _prefill_fn(self, params, cache, tokens, extra, last_pos, slot,
                    pages, rng):
        """Prefill one (bucket-padded) prompt, sample its first token, and
        store the fresh per-request cache: dense leaves scatter into
        slot-table row ``slot``; with the paged layout the decoder KV
        scatters page-wise into the pool through ``pages`` instead (blocks
        the prefix cache already holds are redirected to the null page —
        their physical pages are shared and must never be rewritten).
        Retraces once per distinct padded length (= per bucket)."""
        self.stats["prefill_traces"] += 1
        if self.paged:
            # size the scratch cache to whole pages so the page scatter is
            # a static reshape (bucket padding lands in the null page)
            clen = pages_for(tokens.shape[1], self.page_size) \
                * self.page_size
        else:
            clen = self.max_len
        c1 = self.model.init_cache(self.cfg, 1, clen)
        batch = {"tokens": tokens, **extra}
        if self._bucketed:
            logits, c1 = self.model.prefill(params, batch, self.cfg, c1,
                                            last_pos=last_pos)
        else:
            logits, c1 = self.model.prefill(params, batch, self.cfg, c1)
        tok = sample_tokens(logits[0, -1], rng=rng,
                            temperature=self.temperature)
        out = {}
        for key, big in cache.items():
            if key == "pos":
                out[key] = big.at[slot].set(last_pos + 1)
            elif key == "ptab":
                out[key] = big
            elif key == "kv" and self.paged:
                out[key] = kvcache.scatter_prefill_pages(
                    big, c1[key], pages, self.page_size)
            else:
                out[key] = jax.tree.map(
                    lambda b, o: b.at[:, slot].set(o[:, 0]), big, c1[key])
        return tok, out

    def _mixed_fn(self, params, cache, tokens, pos, slot, active, wnull,
                  rng):
        """ONE device program for a mixed token-slot batch: ``tokens`` is
        a (T, 1) column of T = ``chunk_tokens`` work items — decode
        tokens, prefill-chunk tokens and pads — each tagged with its
        ``pos`` (context position), ``slot`` (page-table row), ``active``
        (sample a token from this row's logits) and ``wnull`` (redirect
        this row's KV write to the null page: the position's KV already
        lives in shared prefix pages, or the row is padding).

        Live rows: a row that samples or writes K/V is live; one that
        does neither is a pad or a recompute of prefix-covered positions,
        and nothing reads its output. The packer puts every live row
        before the pads, so the rows past the last live one go to
        ``decode_step`` as ``rows`` and the paged attention skips them
        (layers.paged_attention's live-row contract) — derived here from
        ``active`` and ``wnull``, so it costs no input of its own.

        Exactness: ``decode_step`` scatters EVERY row's K/V per layer
        before the paged attention gathers, so a chunk's tokens attend to
        each other (and to a same-program donor's chunk) exactly as the
        monolithic prefill would — chunked prefill of a causal decoder is
        bit-identical. T is static and the page-table gather width is
        page-bucketed, so the program retraces once per (token budget,
        page bucket) — the bounded-trace invariant CI asserts. The
        (T, 1) layout keeps a token axis per work item, so multi-token
        speculative decode (ROADMAP #2) widens columns, not the design.
        """
        self.stats["decode_traces"] += 1    # Python side effect: trace-time only
        T = tokens.shape[0]
        ptab_rows = cache["ptab"][slot]               # (T, table_width)
        live = active | ~wnull
        view = {"kv": cache["kv"], "ptab": ptab_rows,
                "wtab": jnp.where(wnull[:, None], 0, ptab_rows),
                "rows": jnp.max(jnp.where(live, jnp.arange(1, T + 1), 0))}
        if "xkv" in cache:
            view["xkv"] = jax.tree.map(lambda a: a[:, slot], cache["xkv"])
        logits, out = self.model.decode_step(params, view, tokens, pos,
                                             self.cfg, **self._attn_kw)
        with jax.named_scope("sample"):
            tok = sample_tokens(logits[:, -1], rng=rng,
                                temperature=self.temperature)
            tok = jnp.where(active, tok, 0)
        new = {"kv": out["kv"], "pos": cache["pos"],
               "ptab": cache["ptab"]}
        if "xkv" in cache:
            new["xkv"] = cache["xkv"]
        return tok, new

    def _encode_fn(self, params, xkv, frames, slot):
        """Audio admission under the mixed step: run the encoder and park
        the per-layer cross-attention K/V in slot ``slot``'s block (the
        legacy path did this inside the monolithic prefill program).
        Takes ONLY the xkv leaves — frame shape and xkv block are fixed
        per config, so the program traces once regardless of how the
        page-table bucket evolves."""
        self.stats["encode_traces"] += 1    # Python side effect: trace-time only
        enc_out = self.model.encode(params, frames, self.cfg)
        xkvs = jax.vmap(
            lambda lp: self.model.cross_kv(lp, enc_out, self.cfg))(
            params["dec_layers"])
        return jax.tree.map(
            lambda big, new: big.at[:, slot].set(new[:, 0]), xkv, xkvs)

    def _next_rng(self):
        if self.temperature <= 0.0:
            return None
        self._rng, key = jax.random.split(self._rng)
        return key

    # --------------------------------------------------------- scheduling
    def submit(self, rid: int, prompt: np.ndarray, max_new: int, *,
               frames: Optional[np.ndarray] = None,
               images: Optional[np.ndarray] = None, priority: int = 0,
               deadline_s: Optional[float] = None):
        """Queue a request. Rejects inputs the engine can NEVER hold —
        prompts at/over ``max_len`` and, on the paged layout, requests
        whose pages can never all come free — instead of deadlocking:
        an unplaceable request would otherwise queue forever at the
        scheduler's head, and head-of-line admission means it would wedge
        everything behind it too. Two bounds, both against the TOTAL
        pool: the MINIMUM admission reservation (lazy: the prompt plus
        its first decode write; eager: the worst case up front) is what
        ``_place`` must satisfy before the first prefill, and the
        WORST-CASE context is what guarantees preemption can always make
        a lone request's extend succeed under lazy growth — the liveness
        argument in serve/scheduler.py. (Transient pressure is not a
        rejection: a request that merely has to WAIT for free pages or a
        free slot stays queued.)

        ``priority`` is the scheduler hint carried on the Request — the
        default FifoLeastProgress policy ignores it; ``scheduler=
        Priority()`` admits higher values first and preempts lower ones
        first.

        ``deadline_s`` declares an SLO: the shipped policies admit the
        nearest deadline first (and give it prefill-budget priority in
        the mixed step), and a request still QUEUED when its deadline
        passes finishes ``done=False, expired=True`` at the next step
        instead of blocking the scheduler's head.

        Returns the LIVE Request record: ``out`` grows as the engine
        decodes, which is what serve/driver.AsyncDriver streams from."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError(f"request {rid}: empty prompt")
        if prompt.size >= self.max_len:
            raise ValueError(
                f"request {rid}: prompt length {prompt.size} >= max_len "
                f"{self.max_len}; the longest servable prompt is "
                f"{self.max_len - 1} tokens")
        if max_new < 1:
            raise ValueError(f"request {rid}: max_new must be >= 1")
        if self.paged:
            cap = min(prompt.size + max_new - 1, self.max_len)
            worst = pages_for(cap, self.page_size)
            if self.lazy:
                need = pages_for(min(prompt.size + 1, cap), self.page_size)
                if need > self.kv_pages:
                    raise ValueError(
                        f"request {rid}: minimum admission reservation is "
                        f"{need} KV pages ({self.page_size} tokens each) "
                        f"but the pool holds {self.kv_pages} — it could "
                        f"never be placed; raise kv_pages or shorten the "
                        f"prompt")
            if worst > self.kv_pages:
                raise ValueError(
                    f"request {rid}: worst-case context needs {worst} KV "
                    f"pages ({self.page_size} tokens each) but the pool "
                    f"holds {self.kv_pages}; raise kv_pages or lower "
                    f"prompt+max_new")
            # pre-book the worst case in the bounded-gather high-water at
            # SUBMIT time: everything accepted before the first decode
            # shares one bucket, and lazy mid-decode extends never
            # re-bucket (_sync_ptab) — only a longer request arriving
            # later can
            self._hw_blocks = max(self._hw_blocks, worst)
        if self.cfg.arch_type == "audio":
            if frames is None:
                raise ValueError(
                    f"request {rid}: {self.cfg.name} is an enc-dec arch; "
                    "submit(..., frames=(encoder_ctx, d_model)) frame "
                    "embeddings (the stubbed audio frontend's output)")
            frames = np.asarray(frames, np.float32)
            want = (self.cfg.encoder_ctx, self.cfg.d_model)
            if frames.shape != want:
                raise ValueError(
                    f"request {rid}: frames shape {frames.shape} != {want}")
        elif frames is not None:
            raise ValueError(
                f"request {rid}: frames are only meaningful for audio "
                f"archs, not {self.cfg.arch_type}")
        if self.cfg.arch_type == "vlm":
            if images is None:
                raise ValueError(
                    f"request {rid}: {self.cfg.name} is a VLM arch; "
                    "submit(..., images=(num_image_tokens, d_model)) "
                    "patch embeddings (the stubbed vision frontend's "
                    "output)")
            images = np.asarray(images, np.float32)
            want = (self.cfg.num_image_tokens, self.cfg.d_model)
            if images.shape != want:
                raise ValueError(
                    f"request {rid}: images shape {images.shape} != {want}")
        elif images is not None:
            raise ValueError(
                f"request {rid}: images are only meaningful for vlm "
                f"archs, not {self.cfg.arch_type}")
        deadline = None
        if deadline_s is not None:
            if deadline_s <= 0:
                raise ValueError(
                    f"request {rid}: deadline_s must be > 0, got "
                    f"{deadline_s}")
            deadline = time.monotonic() + float(deadline_s)
        req = Request(rid, prompt, int(max_new), frames=frames,
                      images=images, priority=int(priority),
                      deadline=deadline)
        self.queue.append(req)
        # span-tree root: the scheduler explains its ordering fields so
        # the trace records WHY admission will pick this request when
        explain = getattr(self._sched, "explain", None)
        self.tracer.req_event(
            rid, "submitted", prompt_tokens=int(prompt.size),
            max_new=int(max_new), queue_depth=len(self.queue),
            **(explain(req) if explain is not None else {}))
        return req

    def _expire_queued(self, now: float):
        """Finish every QUEUED request whose deadline has passed with
        ``done=False, expired=True`` (partial output from a preemption
        rides along) — an expired request must not wedge the scheduler's
        head-of-line contract. Active slots are never expired: their
        pages are committed and finishing them is strictly cheaper than
        wasting the work."""
        if not any(r.deadline is not None for r in self.queue):
            return
        kept: Deque[Request] = deque()
        for req in self.queue:
            if req.deadline is not None and now > req.deadline:
                req.expired = True
                self.finished[req.rid] = req
                self.stats["expired"] += 1
                self.tracer.finish_request(req.rid, "expired",
                                           tokens=len(req.out))
            else:
                kept.append(req)
        self.queue = kept

    def _free_slot(self) -> Optional[int]:
        for s in range(self.slots):
            if self.active[s] is None:
                return s
        return None

    # ------------------------------------------------- paged bookkeeping
    def _sync_ptab(self):
        """Refresh the DEVICE page table from the host mirror, clipped to
        the power-of-two bucket of the live page high-water mark — the
        bounded-gather contract of layers.paged_attention. The mark is
        the max of the allocator's per-owner page high-water and every
        admitted request's WORST-CASE reservation (``_hw_blocks``):
        under eager reservation the two coincide; under lazy growth the
        worst case is pre-booked at admission so mid-decode extends
        never cross a bucket — the one-decode-trace invariant survives
        laziness, and the bound still only re-buckets when a LONGER
        request is admitted. Every live slot's pages fit the bucket (the
        mark dominates every reservation), so no table entry is
        truncated; retired slots' frozen cursors beyond it resolve to
        the null page via the table-width clip in
        kvcache.write_kv_paged."""
        w = page_bucket(max(1, self._hw_blocks,
                            self._alloc.peak_owner_pages), cap=self._pps)
        if w != self._gather:
            self._gather = w
            self._ptab_dirty = True
        if self._ptab_dirty:
            self._cache["ptab"] = self._dev(
                np.ascontiguousarray(self._ptab[:, :w], np.int32))
            self._ptab_dirty = False

    def _note_pool(self):
        used = self._alloc.pages_in_use
        self.stats["pages_in_use"] = used
        if used > self.stats["peak_pages"]:
            self.stats["peak_pages"] = used
        if self._prefix is not None:
            self.stats["prefix_hit_blocks"] = self._prefix.hit_blocks
            self.stats["prefix_miss_blocks"] = self._prefix.miss_blocks
            self.stats["prefix_tail_hits"] = self._prefix.tail_hits

    def _salt(self, req: Request, ctx: np.ndarray):
        """Prefix-cache namespace: blocks are only portable where causal
        KV depends on the prefix alone — enc-dec KV also depends on the
        frames, MoE capacity routing on the whole sequence, so those key
        coarser (identical frames / identical full context). Memoized on
        the request: frames never change, and ``ctx`` (prompt + emitted)
        is uniquely determined by its length over a request's lifetime."""
        if self.cfg.arch_type == "moe":
            if req.salt_cache is None or req.salt_cache[0] != len(ctx):
                req.salt_cache = (len(ctx), ("moe-ctx", hashlib.sha1(
                    np.ascontiguousarray(ctx).tobytes()).hexdigest()))
            return req.salt_cache[1]
        if req.frames is not None:
            if req.salt_cache is None:
                req.salt_cache = (0, ("frames", hashlib.sha1(
                    np.ascontiguousarray(req.frames).tobytes()).hexdigest()))
            return req.salt_cache[1]
        return None

    def _place(self, s: int, req: Request, ctx: np.ndarray):
        """Reserve slot ``s``'s pages for admission: prefix-cache match ->
        adopt shared pages, then draw fresh ones (lazy: prompt + first
        decode page; otherwise the worst case), evicting cold prefix
        blocks when the free-list is short. Returns (block-ordered pages,
        shared head count) or (None, 0) on backpressure."""
        n = len(ctx)
        if self.lazy:
            # the context plus its first decode write — clamped to the
            # request's remaining worst case, which submit() validated
            # against the pool: a request finishing AT admission
            # (max_new reached on the prefill token) never writes a
            # decode token, so demanding its +1 page could deadlock a
            # pool the worst case fits
            reserve = min(n + 1, n + req.max_new - len(req.out) - 1,
                          self.max_len)
        else:
            reserve = min(n + req.max_new - len(req.out) - 1, self.max_len)
        shared: List[int] = []
        salt = None
        if self._prefix is not None:
            salt = self._salt(req, ctx)
            # partial-tail adoption forces a copy-on-write at the first
            # decode write; only lazy mode has the mid-decode alloc path
            # (and its reclaim ladder) to pay for that copy.
            full_pages, tail_page, _ = self._prefix.match(
                ctx, salt=salt, want_tail=self.lazy)
            shared = list(full_pages)
            if tail_page is not None:
                shared.append(tail_page)
        got = self._alloc.alloc(s, reserve, shared=shared)
        if got is None and self._prefix is not None:
            need = (pages_for(reserve, self.page_size) - len(shared)
                    - self._alloc.free_pages)
            keep = frozenset(shared)
            # only spend cached blocks when evicting can actually cover
            # the shortfall — otherwise the request waits for retirements
            # anyway and the flushed blocks would have bought nothing
            if 0 < need <= self._prefix.evictable_pages(keep=keep):
                while need > 0 and self._prefix.evict_one(keep=keep):
                    self.stats["prefix_evictions"] += 1
                    need -= 1
                got = self._alloc.alloc(s, reserve, shared=shared)
        if got is None:
            return None, 0
        if self._prefix is not None:
            # count reuse on SUCCESSFUL adoption only (a backpressured
            # head-of-line request re-matches every step)
            full = len(shared) - (1 if tail_page is not None else 0)
            self._prefix.hit_blocks += full
            self._prefix.miss_blocks += n // self.page_size - full
            if tail_page is not None:
                self._prefix.tail_hits += 1
            # register this context's freshly written full blocks so the
            # NEXT request (or this one's re-admission) shares them
            self._prefix.insert(ctx, got, salt=salt)
        self._note_pool()
        return got, len(shared)

    def _admit(self):
        while True:
            qi = self._sched.next_index(self.queue)
            if qi is None:
                return
            s = self._free_slot()
            if s is None:
                return
            req = self.queue[qi]
            # a preempted request resumes by prefilling prompt + emitted
            ctx = req.prompt if not req.out else np.concatenate(
                [req.prompt, np.asarray(req.out, np.int32)])
            n = len(ctx)
            blen = prefill_bucket(n, cap=self._window) if self._bucketed \
                else n
            pages = None
            if self.paged:
                got, n_shared = self._place(s, req, ctx)
                if got is None:
                    # head-of-line: WAIT for retirements/evictions instead
                    # of admitting around the scheduler's pick
                    return
                self._ptab[s] = 0
                self._ptab[s, :len(got)] = got
                self._ptab_dirty = True
                npb = pages_for(blen, self.page_size)
                page_vec = np.zeros(npb, np.int64)
                m = min(npb, len(got))
                page_vec[:m] = got[:m]
                # shared head pages already hold this prefix's KV — the
                # prefill scatter must not rewrite pages other slots read;
                # redirect those blocks to the null page
                page_vec[:min(n_shared, npb)] = 0
                pages = self._dev(page_vec.astype(np.int32))
            if qi == 0:
                self.queue.popleft()
            else:
                del self.queue[qi]
            self.tracer.req_event(req.rid, "admitted", slot=s,
                                  ctx_tokens=n, resumed=bool(req.out))
            if self.paged:
                self._sync_ptab()
            padded = np.zeros(blen, np.int32)
            padded[:n] = ctx
            extra = {}
            if req.frames is not None:
                extra["frames"] = self._dev(req.frames[None])
            if req.images is not None:
                extra["image_embeds"] = self._dev(req.images[None])
            with self._ctx():
                tok, self._cache = self._prefill(
                    self.params, self._cache, self._dev(padded[None]), extra,
                    self._dev(np.int32(n - 1)), self._dev(np.int32(s)),
                    pages, self._next_rng())
            self.stats["prefills"] += 1
            self.stats["decode_tokens"] += 1
            tok = int(tok)
            req.out.append(tok)
            self.tracer.req_tokens(req.rid, 1)
            if req.first_tok_t is None:
                req.first_tok_t = time.monotonic()
                self.tracer.req_event(req.rid, "first_token",
                                      prefill_tokens=n)
            self._pos[s] = n
            self._last[s] = tok
            # honor max_new / EOS / capacity on the PREFILL-sampled token:
            # a request that is already complete never occupies a slot (or
            # pages), so output length is exactly min(max_new,
            # tokens-until-EOS)
            hit_eos = self.eos_id is not None and tok == self.eos_id
            if len(req.out) >= req.max_new or hit_eos or n >= self.max_len:
                req.done = True
                self.finished[req.rid] = req
                self.tracer.finish_request(req.rid, "completed",
                                           tokens=len(req.out))
                if self.paged:
                    self._release_pages(s)
            else:
                self.active[s] = req

    # ------------------------------------------- mixed (chunked) admission
    def _place_mixed(self, s: int, req: Request, ctx: np.ndarray):
        """Reserve slot ``s``'s pages for MIXED admission — like
        :meth:`_place` but prefill happens later, chunk by chunk, so the
        radix tree is NOT updated here (the step loop inserts
        progressively as the cursor passes block boundaries) and the
        match can additionally adopt pages from a slot still
        MID-PREFILL over the same context (the tree only knows blocks a
        donor's cursor already passed). Returns ``(pages, covered,
        dep)``: ``covered`` counts context tokens whose KV this slot
        must NOT rewrite (shared pages), ``dep`` is ``(donor_slot,
        needed_tokens)`` when some of that coverage is still being
        written by a donor — the budget packer holds this slot's chunks
        until the donor's planned cursor reaches ``needed_tokens``.
        ``(None, 0, None)`` on backpressure."""
        n = len(ctx)
        if self.lazy:
            reserve = min(n + 1, n + req.max_new - len(req.out) - 1,
                          self.max_len)
        else:
            reserve = min(n + req.max_new - len(req.out) - 1, self.max_len)
        ps = self.page_size
        shared: List[int] = []
        covered = 0
        dep = None
        salt = None
        tail_page = None
        if self._prefix is not None:
            salt = self._salt(req, ctx)
            full_pages, tail_page, _ = self._prefix.match(
                ctx, salt=salt, want_tail=self.lazy)
            shared = list(full_pages)
            covered = len(full_pages) * ps
            if tail_page is not None:
                # a matched tail block covers the ENTIRE remaining
                # context (prefix.match's contract), so nothing is left
                # to prefill-write; CoW duplicates it before the first
                # decode write (lazy-only, as on the legacy path)
                shared.append(tail_page)
                covered = n
            elif covered < n:
                # in-flight donor: a mid-prefill slot over the same
                # context extends coverage beyond the tree
                for d, st in self._pf.items():
                    if st["salt"] != salt:
                        continue
                    dctx = st["ctx"]
                    lim = min(n, len(dctx)) // ps * ps
                    m = covered
                    while m + ps <= lim and np.array_equal(
                            ctx[m:m + ps], dctx[m:m + ps]):
                        m += ps
                    if m > covered:
                        dpages = self._alloc.pages_of(d)
                        shared.extend(dpages[covered // ps:m // ps])
                        dep = (d, m)
                        covered = m
                        break
        got = self._alloc.alloc(s, reserve, shared=shared)
        if got is None and self._prefix is not None:
            need = (pages_for(reserve, ps) - len(shared)
                    - self._alloc.free_pages)
            keep = frozenset(shared)
            if 0 < need <= self._prefix.evictable_pages(keep=keep):
                while need > 0 and self._prefix.evict_one(keep=keep):
                    self.stats["prefix_evictions"] += 1
                    need -= 1
                got = self._alloc.alloc(s, reserve, shared=shared)
        if got is None:
            return None, 0, None
        if self._prefix is not None:
            # every covered block — tree hit or in-flight adoption — is
            # prefill work this request skips
            full = covered // ps if tail_page is None else len(full_pages)
            self._prefix.hit_blocks += full
            self._prefix.miss_blocks += n // ps - full
            if tail_page is not None:
                self._prefix.tail_hits += 1
        self._note_pool()
        return got, covered, dep

    def _admit_mixed(self):
        """Mixed-step admission: place pages and ENQUEUE the prefill work
        (no device call here — the step loop drains it chunk by chunk
        through the one mixed program). The slot is active immediately;
        its first token is sampled on the chunk containing the final
        prompt position."""
        while True:
            qi = self._sched.next_index(self.queue)
            if qi is None:
                return
            s = self._free_slot()
            if s is None:
                return
            req = self.queue[qi]
            ctx = req.prompt if not req.out else np.concatenate(
                [req.prompt, np.asarray(req.out, np.int32)])
            n = len(ctx)
            got, covered, dep = self._place_mixed(s, req, ctx)
            if got is None:
                return
            self._ptab[s] = 0
            self._ptab[s, :len(got)] = got
            self._ptab_dirty = True
            if qi == 0:
                self.queue.popleft()
            else:
                del self.queue[qi]
            if req.frames is not None:
                with self._ctx():
                    self._cache["xkv"] = self._encode(
                        self.params, self._cache["xkv"],
                        self._dev(req.frames[None]),
                        self._dev(np.int32(s)))
            self.active[s] = req
            self._pos[s] = 0
            self._last[s] = 0
            self.tracer.req_event(req.rid, "admitted", slot=s,
                                  ctx_tokens=n, covered=int(covered),
                                  resumed=bool(req.out))
            # cursor = next context position to compute; covered KV is
            # skipped EXCEPT the final prompt token, which must run for
            # its first-token logits (its write goes to the null page)
            self._pf[s] = {
                "ctx": ctx, "n": n, "cursor": int(min(covered, n - 1)),
                "covered": int(covered), "dep": dep,
                "salt": (self._salt(req, ctx)
                         if self._prefix is not None else None),
                "seq": self._admit_seq}
            self._admit_seq += 1

    def _release_pages(self, s: int):
        """Drop slot ``s``'s page references (shared prefix pages stay
        live for their other holders / the prefix cache) and point its
        table row at the null page so any frozen-cursor write lands in
        scratch."""
        self._alloc.free(s)
        self._ptab[s] = 0
        self._ptab_dirty = True
        self._note_pool()

    def _retire(self, s: int):
        req = self.active[s]
        req.done = True
        self.finished[req.rid] = req
        self.tracer.finish_request(req.rid, "completed",
                                   tokens=len(req.out))
        self.active[s] = None
        if self.paged:
            self._release_pages(s)

    # -------------------------------------------- lazy growth + CoW + preempt
    def _preempt(self, s: int):
        """Evict slot ``s`` mid-decode: release its pages (prefix pages
        merely drop a reference and usually stay cached) and requeue the
        request, partial output intact, for re-prefill. A MID-PREFILL
        victim (mixed step) additionally cascades to any dependent slot
        that adopted its pages beyond what its cursor wrote — that KV
        will never exist, so the dependent re-prefills too."""
        req = self.active[s]
        st = self._pf.pop(s, None)
        self.active[s] = None
        if self.paged:
            self._release_pages(s)
        self._sched.requeue(self.queue, req)
        self.stats["preemptions"] += 1
        self.tracer.req_preempted(req.rid, slot=s, tokens=len(req.out),
                                  mid_prefill=st is not None)
        if st is not None:
            for d, dst in list(self._pf.items()):
                if dst["dep"] is not None and dst["dep"][0] == s \
                        and st["cursor"] < dst["dep"][1]:
                    self._preempt(d)

    def preempt(self, s: int):
        """Public cancel-and-requeue of slot ``s`` (any KV layout): the
        watchdog's recovery path (serve/driver.py). The request keeps its
        partial output and resumes by re-prefill — greedy decode is
        bit-identical to the uninterrupted run."""
        if not 0 <= s < self.slots or self.active[s] is None:
            raise ValueError(f"slot {s} holds no active request")
        self._preempt(s)

    def _reclaim_one(self, needy: int) -> bool:
        """Free pool capacity for slot ``needy``: evict one cold prefix
        block if possible, else preempt the scheduler's victim. Returns
        False when ``needy`` itself was preempted or nothing is left to
        reclaim (the caller must skip the slot this step)."""
        if self._prefix is not None and self._prefix.evict_one():
            self.stats["prefix_evictions"] += 1
            return True
        victims = [(t, len(self.active[t].out), self.active[t].priority)
                   for t in range(self.slots) if self.active[t] is not None]
        if not victims:
            return False
        v = self._sched.pick_victim(victims)
        self._preempt(v)
        return v != needy

    def _extend_reclaiming(self, s: int, n_tokens: int):
        """allocator.extend with the reclaim ladder. Returns the fresh
        pages, or None when slot ``s`` was preempted to satisfy itself."""
        while True:
            fresh = self._alloc.extend(s, n_tokens)
            if fresh is not None:
                self._note_pool()
                return fresh
            if not self._reclaim_one(s):
                return None

    def _cow_reclaiming(self, s: int, blk: int) -> bool:
        """Copy-on-write slot ``s``'s page at ``blk`` (allocator swap +
        device page copy), reclaiming if no page is free. Returns False
        when slot ``s`` was preempted instead."""
        while True:
            old = self._alloc.pages_of(s)[blk]
            new = self._alloc.cow(s, blk)
            if new is not None:
                if new != old:
                    with self._ctx():
                        self._cache["kv"] = self._copy_page(
                            self._cache["kv"], self._dev(np.int32(old)),
                            self._dev(np.int32(new)))
                    self._ptab[s, blk] = new
                    self._ptab_dirty = True
                    self.stats["cow_copies"] += 1
                    self._note_pool()
                return True
            if not self._reclaim_one(s):
                return False

    def _grow_and_cow(self):
        """Before the batched decode writes at each slot's cursor: grow
        lazy reservations across page boundaries and copy-on-write any
        write-target page that is still shared. Either can preempt slots
        (including the needy one) when the pool runs dry."""
        ps = self.page_size
        for s in range(self.slots):
            # mid-prefill slots (mixed step) neither decode-write nor
            # grow this step — and their shared head blocks must NOT be
            # CoW'd (pos is still 0, but the block is a prefix hit)
            if self.active[s] is None or s in self._pf:
                continue
            pos = int(self._pos[s])
            if self.lazy and \
                    pages_for(pos + 1, ps) > len(self._alloc.pages_of(s)):
                fresh = self._extend_reclaiming(s, pos + 1)
                if fresh is None:
                    continue                  # s was preempted
                w = len(self._alloc.pages_of(s))
                self._ptab[s, w - len(fresh):w] = fresh
                self._ptab_dirty = True
            own = self._alloc.pages_of(s)
            blk = pos // ps
            if blk < len(own) and self._alloc.refcount(own[blk]) > 1:
                self._cow_reclaiming(s, blk)

    # ------------------------------------------------ speculative decode
    def _propose_drafts(self, decode_slots):
        """Ask the drafter for up to ``spec.k`` continuation tokens per
        decoding slot and reserve the pages their KV writes need.

        ``k_s`` is clamped so every drafted position stays inside the
        request's own remaining budget (``max_new``) and the context cap
        — which keeps the write positions inside the worst-case
        reservation submit() pre-booked in ``_hw_blocks``, so drafting
        NEVER re-buckets the bounded gather (trace count stays one per
        (token budget, page bucket), speculation on or off). Under lazy
        growth the reservation is extended to cover the draft writes up
        front with a PLAIN extend — speculation is opportunistic and
        must never evict prefix blocks or preempt a neighbour to place
        a guess, so a dry pool just truncates the draft (the rejection
        path returns these pages via ``PageAllocator.rollback``). Eager
        reservations already hold the worst case and are never touched.
        """
        drafts = {}
        for s in decode_slots:
            req = self.active[s]
            P = int(self._pos[s])
            k_s = min(self.spec.k, req.max_new - len(req.out) - 1,
                      self.max_len - 1 - P)
            if k_s < 1:
                continue
            ctx = np.concatenate(
                [req.prompt, np.asarray(req.out, np.int64)])
            d = np.asarray(self._drafter.propose(ctx, k_s),
                           np.int64).reshape(-1)[:k_s]
            if len(d) and self.lazy:
                if self._alloc.extend(s, P + 1 + len(d)) is None:
                    # pool dry: keep only the drafts whose writes fit
                    # the pages already held (possibly none)
                    room = (len(self._alloc.pages_of(s)) * self.page_size
                            - P - 1)
                    d = d[:max(room, 0)]
                    if len(d):
                        self._alloc.extend(s, P + 1 + len(d))
                if len(d):
                    own = self._alloc.pages_of(s)
                    self._ptab[s, :len(own)] = own
                    self._ptab_dirty = True
                    self._note_pool()
            if len(d):
                drafts[s] = d
        return drafts

    def release_prefix_cache(self) -> int:
        """Flush every prefix block no live request still shares, freeing
        their pages. Returns the number of blocks evicted."""
        if self._prefix is None:
            return 0
        n = self._prefix.flush()
        self.stats["prefix_evictions"] += n
        self._note_pool()
        return n

    # -------------------------------------------------------------- serve
    def step(self) -> int:
        """Advance the engine by one step. MIXED engines (the default on
        the paged layout) run ONE token-slot program covering every
        active slot's decode token plus prefill chunks inside the
        ``chunk_tokens`` budget (:meth:`_step_mixed`); legacy engines
        admit-with-synchronous-prefill then run the batched decode.
        Returns the number of tokens produced this step — the
        AsyncDriver's streaming signal. Step timing lands in ``stats``:
        ``step_count`` and ``wall_time_s`` cover every call, and
        ``tokens_per_s_ewma`` smooths the produced-tokens rate (alpha
        0.2) for the DP router's latency-aware routing."""
        if self.mixed:
            return self._step_mixed()
        t0 = time.perf_counter()
        before = self.stats["decode_tokens"]
        tr = self.tracer.begin_step(self.stats["step_count"])
        self._expire_queued(time.monotonic())
        # the legacy path prefills synchronously inside admission (its
        # own device program), so it gets its own phase label instead of
        # hiding inside bookkeeping
        tr.enter("admit")
        self._admit()
        tr.enter("bookkeeping")
        if self.paged and (self.lazy or self._prefix is not None):
            self._grow_and_cow()
        mask = np.array([r is not None for r in self.active])
        if mask.any():
            tr.enter("pack")
            if self.paged:
                tr.sub("inputs")
                self._sync_ptab()
            tr.enter("dispatch")
            with self._ctx():
                tr.sub("inputs")
                args = (self._dev(self._last[:, None].astype(np.int32)),
                        self._dev(self._pos.astype(np.int32)),
                        self._dev(mask), self._next_rng())
                tr.sub("launch")
                tok, self._cache = self._decode(self.params, self._cache,
                                                *args)
            toks = self._read_tokens(tok, tr)
            self.stats["decode_steps"] += 1
            self.stats["decode_slot_steps"] += int(mask.sum())
            for s in range(self.slots):
                req = self.active[s]
                if req is None:
                    continue
                t = int(toks[s])
                req.out.append(t)
                self._pos[s] += 1
                self._last[s] = t
                self.stats["decode_tokens"] += 1
                tr.note_decode(s, req.rid, 1)
                self.tracer.req_tokens(req.rid, 1)
                self.tracer.req_detail(req.rid, "decode", slot=s,
                                       pos=int(self._pos[s]))
                if self._prefix is not None and \
                        self._pos[s] % self.page_size == 0:
                    self._register_decode_block(s, req)
                hit_eos = self.eos_id is not None and t == self.eos_id
                if len(req.out) >= req.max_new or hit_eos or \
                        self._pos[s] >= self.max_len:
                    self._retire(s)
        return self._finish_step(t0, before, tr)

    @staticmethod
    def _read_tokens(tok, tr) -> np.ndarray:
        """The ``sync`` phase: wait for the step program's tokens, then
        copy them to the host, each a child span of its own; the step
        goes back to ``bookkeeping`` after."""
        tr.enter("sync")
        tr.sub("wait")
        tok.block_until_ready()
        tr.sub("readback")
        toks = np.asarray(tok)
        tr.enter("bookkeeping")
        return toks

    def _finish_step(self, t0: float, before: int, tr=NULL_STEP) -> int:
        """Shared step epilogue: token count + timing telemetry, and the
        step's trace record (residual time folds into bookkeeping so the
        phases partition the whole step)."""
        produced = self.stats["decode_tokens"] - before
        tr.enter("bookkeeping")
        self.tracer.end_step(tr, produced)
        dt = time.perf_counter() - t0
        self.stats["step_count"] += 1
        self.stats["wall_time_s"] += dt
        if produced and dt > 0:
            rate = produced / dt
            ewma = self.stats["tokens_per_s_ewma"]
            self.stats["tokens_per_s_ewma"] = \
                rate if ewma <= 0 else 0.8 * ewma + 0.2 * rate
        return produced

    # ---- observability surface (delegates to the tracer) -------------

    def trace(self) -> dict:
        """Chrome ``trace_event`` JSON object for this engine's tracer."""
        return chrome_trace([self.tracer])

    def export_trace(self, path: str) -> dict:
        """Write the Chrome/Perfetto trace JSON to ``path``."""
        return export_chrome_trace(path, [self.tracer])

    def flight(self, last: int | None = None) -> dict:
        """Flight-recorder snapshot (recent step records + spans)."""
        return self.tracer.flight(last)

    def _step_mixed(self) -> int:
        """One MIXED token-slot step (the tentpole refactor): expire
        overdue queued requests, admit into free slots (pages only — no
        synchronous prefill), then fill the ``chunk_tokens`` budget with
        every decoding slot's next token FIRST and prefill chunks of
        mid-prefill slots after (scheduler's ``prefill_key`` order,
        nearest deadline first), and run the whole batch as ONE device
        program. A slot's first token is sampled on the chunk containing
        its final prompt position; admission runs again at the END so a
        request finishing at admission frees its slot for the same-step
        queue (matching the legacy path's same-step admission cadence).
        """
        t0 = time.perf_counter()
        before = self.stats["decode_tokens"]
        tr = self.tracer.begin_step(self.stats["step_count"])
        abort = self.abort_event
        if abort is not None and abort.is_set():
            # chunk-boundary cancellation (watchdog): skip launching this
            # step's program entirely — control returns to the driver at
            # sub-step latency and recovery requeues the slots
            return self._finish_step(t0, before, tr)
        self._expire_queued(time.monotonic())
        self._admit_mixed()
        if self.lazy or self._prefix is not None:
            self._grow_and_cow()
        tr.enter("draft")
        # clear satisfied dependencies: the donor finished its prefill
        # (left _pf with full coverage) or its cursor passed the needed
        # point; a donor preempted EARLIER already cascaded (see
        # _preempt), so absence means satisfied
        for st in self._pf.values():
            if st["dep"] is not None:
                d, needed = st["dep"]
                dst = self._pf.get(d)
                if dst is None or dst["cursor"] >= needed:
                    st["dep"] = None
        decode_slots = [s for s in range(self.slots)
                        if self.active[s] is not None and s not in self._pf]
        drafts = self._propose_drafts(decode_slots) \
            if self._drafter is not None else {}
        tr.enter("pack")
        pkey = getattr(self._sched, "prefill_key", None)
        items = sorted(
            self._pf.items(),
            key=lambda kv: ((pkey(self.active[kv[0]])
                             if pkey is not None else ()), kv[1]["seq"]))
        allot = pack_token_budget(
            self.chunk_tokens,
            [1 + len(drafts.get(s, ())) for s in decode_slots],
            [{"slot": s, "cursor": st["cursor"], "n": st["n"],
              "dep": st["dep"]} for s, st in items])
        if not decode_slots and not allot:
            tr.enter("bookkeeping")
            self._admit_mixed()
            return self._finish_step(t0, before, tr)
        T = self.chunk_tokens
        tokens = np.zeros((T, 1), np.int32)
        pos = np.zeros(T, np.int32)
        slot_v = np.zeros(T, np.int32)
        active = np.zeros(T, bool)
        wnull = np.ones(T, bool)      # pads write to the null page
        r = 0
        base_row: Dict[int, int] = {}
        draft_rows: Dict[int, List[int]] = {}
        for s in decode_slots:
            tokens[r, 0] = self._last[s]
            pos[r] = self._pos[s]
            slot_v[r] = s
            active[r] = True
            wnull[r] = False
            base_row[s] = r
            r += 1
            # speculative draft rows: same slot, consecutive positions.
            # Draft row i carries drafted token d[i] at position P+1+i;
            # its logits are the verifier's token for position P+2+i —
            # valid exactly when d[0..i] all matched (the accept loop's
            # prefix rule). KV order is exact: _mixed_fn scatters EVERY
            # row's K/V before the attention gathers, and a row at
            # position p attends to kv_len p+1, so the base row never
            # sees draft KV while draft row i sees the base write and
            # drafts 0..i-1.
            for i, t in enumerate(drafts.get(s, ())):
                tokens[r, 0] = t
                pos[r] = self._pos[s] + 1 + i
                slot_v[r] = s
                active[r] = True
                wnull[r] = False
                draft_rows.setdefault(s, []).append(r)
                r += 1
        emit_row: Dict[int, int] = {}
        for s, start, count in allot:
            st = self._pf[s]
            ctx, cov, last = st["ctx"], st["covered"], st["n"] - 1
            for p in range(start, start + count):
                tokens[r, 0] = ctx[p]
                pos[r] = p
                slot_v[r] = s
                wnull[r] = p < cov
                if p == last:
                    active[r] = True
                    emit_row[s] = r
                r += 1
        if abort is not None and abort.is_set():
            # the watchdog fired while admission/encode/grow ran: yield
            # at this chunk boundary instead of launching the program
            return self._finish_step(t0, before, tr)
        # the live-row count _mixed_fn derives, and the attention width
        live = np.flatnonzero(active | ~wnull)
        n_live = int(live[-1]) + 1 if live.size else 0
        width = row_width(n_live, T)
        self.stats[f"attn_steps_w{width}"] += 1
        tr.note_rows(n_live, width, T)
        tr.sub("inputs")
        self._sync_ptab()
        tr.enter("dispatch")
        with self._ctx():
            tr.sub("inputs")
            args = (self._dev(tokens), self._dev(pos), self._dev(slot_v),
                    self._dev(active), self._dev(wnull), self._next_rng())
            tr.sub("launch")
            tok, self._cache = self._mixed(self.params, self._cache, *args)
        toks = self._read_tokens(tok, tr)
        if decode_slots:
            self.stats["decode_steps"] += 1
            self.stats["decode_slot_steps"] += len(decode_slots)
        for s in decode_slots:
            req = self.active[s]
            d = drafts.get(s, ())
            # greedy acceptance: the base row's argmax is ALWAYS the true
            # next token (bit-identical to non-speculative decode); draft
            # i's logits are valid iff d[0..i] matched the chain so far,
            # so accept the longest matching prefix plus the verifier's
            # one bonus token after it.
            accepted = [int(toks[base_row[s]])]
            m = 0
            while m < len(d) and int(d[m]) == accepted[-1]:
                accepted.append(int(toks[draft_rows[s][m]]))
                m += 1
            self.stats["spec_drafted"] += len(d)
            self.stats["spec_accepted"] += m
            # consume token-by-token, exactly mirroring the non-spec
            # epilogue: max_new / EOS / capacity stop the chain mid-draft
            # (output length stays min(max_new, tokens-until-EOS)).
            emitted = 0
            retired = False
            for t in accepted:
                req.out.append(t)
                self._pos[s] += 1
                self._last[s] = t
                self.stats["decode_tokens"] += 1
                # count BEFORE a possible retire: finish_request seals the
                # span, so the token total must already be up to date
                self.tracer.req_tokens(req.rid, 1)
                emitted += 1
                if self._prefix is not None and \
                        self._pos[s] % self.page_size == 0:
                    self._register_decode_block(s, req)
                hit_eos = self.eos_id is not None and t == self.eos_id
                if len(req.out) >= req.max_new or hit_eos or \
                        self._pos[s] >= self.max_len:
                    # detail event first — retiring seals the span tree
                    self.tracer.req_detail(req.rid, "decode", slot=s,
                                           tokens=emitted,
                                           drafted=len(d), accepted=m)
                    self._retire(s)
                    retired = True
                    break
            if not retired:
                self.tracer.req_detail(req.rid, "decode", slot=s,
                                       tokens=emitted, drafted=len(d),
                                       accepted=m)
            tr.note_decode(s, req.rid, emitted, drafted=len(d), accepted=m)
            if self.lazy and len(d) and self.active[s] is req:
                # rejection rollback: drop draft pages beyond the
                # accepted cursor (retired slots already freed all pages)
                # and restore the lazy invariant _len == pos; the freed
                # tail is always this step's extend-fresh private pages,
                # so shared/prefix pages are never touched. The stale KV
                # inside kept pages is invisible (kv_len masks by pos)
                # and overwritten before the cursor passes it.
                dropped = self._alloc.rollback(s, int(self._pos[s]))
                if dropped:
                    w = len(self._alloc.pages_of(s))
                    self._ptab[s, w:w + len(dropped)] = 0
                    self._ptab_dirty = True
                self._note_pool()
        ps = self.page_size
        for s, start, count in allot:
            st = self._pf[s]
            st["cursor"] = start + count
            self.stats["prefill_chunk_tokens"] += count
            rid = self.active[s].rid
            tr.note_chunk(s, rid, start, count)
            self.tracer.req_chunk_tokens(rid, count)
            self.tracer.req_detail(rid, "prefill_chunk", slot=s,
                                   start=start, count=count)
            if self._prefix is not None:
                # progressive registration: only blocks the cursor has
                # fully passed — a later request (or a preemption
                # cascade) must never adopt an unwritten block
                aligned = st["cursor"] // ps * ps
                if aligned > 0:
                    self._prefix.insert(st["ctx"][:aligned],
                                        self._alloc.pages_of(s),
                                        salt=st["salt"])
            if st["cursor"] > st["n"] - 1:
                # final chunk ran the last prompt position: emit the
                # first token and flip the slot to decoding
                del self._pf[s]
                req = self.active[s]
                t = int(toks[emit_row[s]])
                self.stats["prefills"] += 1
                self.stats["decode_tokens"] += 1
                req.out.append(t)
                self.tracer.req_tokens(req.rid, 1)
                if req.first_tok_t is None:
                    req.first_tok_t = time.monotonic()
                    self.tracer.req_event(req.rid, "first_token",
                                          prefill_tokens=int(st["n"]))
                self._pos[s] = st["n"]
                self._last[s] = t
                hit_eos = self.eos_id is not None and t == self.eos_id
                if len(req.out) >= req.max_new or hit_eos or \
                        st["n"] >= self.max_len:
                    req.done = True
                    self.finished[req.rid] = req
                    self.active[s] = None
                    self._release_pages(s)
                    self.tracer.finish_request(req.rid, "completed",
                                               tokens=len(req.out))
        self._admit_mixed()
        return self._finish_step(t0, before, tr)

    def _register_decode_block(self, s: int, req: Request):
        """DECODE-GENERATED prefix registration: slot ``s``'s cursor just
        crossed a page boundary, so the page holding the latest block is
        complete — register it in the radix tree under the same per-arch
        exactness salt the prompt path uses, and a repeat continuation
        (or this request's own post-preemption re-prefill) adopts it
        instead of recomputing. Only the WRITTEN context counts: KV
        exists for positions 0..pos-1 = prompt + out[:-1] (the newest
        sampled token is the next step's input)."""
        ctx = np.concatenate([req.prompt, np.asarray(req.out[:-1],
                                                     np.int32)])
        self.stats["prefix_decode_blocks"] += self._prefix.insert(
            ctx, self._alloc.pages_of(s), salt=self._salt(req, ctx))

    def reset_stats(self):
        """Zero the telemetry counters so benches measure steady state
        instead of since-construction — EXCEPT the trace counters
        (``decode_traces``/``prefill_traces``): those assert program
        identity over the engine's lifetime (the one-trace-per-bucket CI
        property) and stay monotonic. Pool gauges restart from the
        current occupancy; the prefix cache's hit/miss counters restart
        from zero."""
        keep = ("decode_traces", "prefill_traces", "encode_traces",
                "decode_backend")
        for k, v in self.stats.items():
            if k not in keep:
                self.stats[k] = 0.0 if isinstance(v, float) else 0
        if self._prefix is not None:
            self._prefix.hit_blocks = 0
            self._prefix.miss_blocks = 0
            self._prefix.tail_hits = 0
        if self.paged:
            self._note_pool()
            self.stats["peak_pages"] = self.stats["pages_in_use"]

    def run(self, max_steps: int = 10_000) -> Dict[int, Request]:
        """Serve until the queue and slot table drain (or ``max_steps``).

        Returns every submitted request's record: completed ones with
        ``done=True``, still-active ones with their partial output and
        still-queued ones with ``out == []`` (both ``done=False``) when
        ``max_steps`` is exhausted — nothing vanishes."""
        steps = 0
        while self.busy() and steps < max_steps:
            self.step()
            steps += 1
        return self.results()

    def busy(self) -> bool:
        """True while any request is queued or mid-decode."""
        return bool(self.queue) or any(r is not None for r in self.active)

    def results(self) -> Dict[int, Request]:
        """Every submitted request's record so far: finished, active
        (partial ``out``) and queued (``out == []``) — nothing vanishes.
        Shared by :meth:`run` and serve/parallel.ReplicaRouter."""
        results = dict(self.finished)
        for req in list(self.active) + list(self.queue):
            if req is not None:
                results[req.rid] = req
        return results
