"""Serving launcher: spin up the continuous-batching engine (through
``repro.api.Session`` — the session owns param init) and stream a
synthetic request workload through it.

By default the registry config is served at its published widths and in
its own dtype, with random weights; ``--smoke`` serves the reduced
float32 variant instead, which is what runs on a CPU:

    PYTHONPATH=src python -m repro.launch.serve --arch minitron-4b
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-14b --smoke \
        --requests 6 --max-new 12

The engine defaults to the paged (block-table) KV cache wherever it is
exact; ``--dense`` forces the contiguous per-slot layout, ``--page-size``
/ ``--kv-pages`` shape the paged pool, ``--prefix-cache`` shares common
prompt prefixes copy-on-write (pair with ``--shared-prefix N`` for a
visible hit rate), and ``--lazy`` grows reservations on page-boundary
crossings with preempt/requeue under pressure. Audio (enc-dec) archs
serve with synthetic frame embeddings standing in for the stubbed
mel+conv frontend; VLM archs likewise serve with synthetic image patch
embeddings (the stubbed ViT+projector's output). On the paged layout
the engine steps in MIXED mode by default — one program per step over a
``--chunk-tokens`` token budget shared between decode and chunked
prefill (``--no-mixed`` restores the legacy split prefill/decode
programs); ``--spec-k K`` adds speculative multi-token decode — up to K
self-drafted tokens per slot verified in the same dispatch
(``--drafter ngram|model``), greedy output bit-identical.

Parallel serving (serve/parallel.py): ``--tp N`` shards the one-trace
decode program over N devices (Megatron layout, head-sharded KV pool),
``--dp M`` replicates the engine M times behind a least-load router —
``--tp 2 --dp 2`` needs 4 devices. The session is built on that
``(dp, tp)`` mesh, so the parameters are born sharded and never
replicated onto every device first. Under ``JAX_PLATFORMS=cpu`` the
launcher forces 8 virtual devices up front (before jax initializes) so
both flags work on a CPU host; set XLA_FLAGS yourself to override.

ONLINE mode (``--serve``) skips the synthetic batch and stands up the
HTTP front-end (serve/server.py) on ``--port`` instead: ``POST
/generate`` with optional chunked token streaming, ``GET /metrics``
(Prometheus text — TTFT/TPOT p50/p90/p99, step latency, pool/prefix/
preemption counters), ``GET /healthz``; ``--watchdog-timeout`` arms the
stalled-step watchdog (diagnostic dump + cancel-and-requeue recovery).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-14b --smoke \
        --serve --port 8000 --watchdog-timeout 30
    curl -s localhost:8000/generate -d '{"prompt": [1,2,3], "max_new": 8}'
    curl -s localhost:8000/metrics | grep serve_ttft
"""
from __future__ import annotations

import os
import sys

if os.environ.get("JAX_PLATFORMS") == "cpu" and \
        any(a.startswith(("--tp", "--dp")) for a in sys.argv):
    # must land before jax (imported below via repro.api) initializes
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")

import argparse
import time

import jax
import numpy as np

from repro.api import Session
from repro.configs import ARCH_NAMES, get_config, get_smoke
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, default="minitron-4b")
    ap.add_argument("--smoke", action="store_true",
                    help="serve the reduced float32 smoke variant of the "
                         "arch (default: its published widths and dtype)")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy; >0 = on-device temperature sampling")
    ap.add_argument("--dense", action="store_true",
                    help="force the dense per-slot KV layout "
                         "(default: paged block tables where exact)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (paged layout)")
    ap.add_argument("--kv-pages", type=int, default=None,
                    help="shared pool size in pages (default: dense-"
                         "capacity parity, slots*ceil(max_len/page_size))")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share one physical copy of common prompt "
                         "prefixes via refcounted pages (paged layout)")
    ap.add_argument("--lazy", action="store_true",
                    help="lazy page growth: reserve prompt + one decode "
                         "page at admission, grow on page-boundary "
                         "crossings, preempt/requeue when the pool runs "
                         "dry (paged layout)")
    ap.add_argument("--no-mixed", action="store_true",
                    help="disable the unified mixed token-slot step and "
                         "run the legacy split prefill/decode programs "
                         "(mixed is the default on the paged layout)")
    ap.add_argument("--chunk-tokens", type=int, default=256,
                    help="mixed step token budget: decode tokens for all "
                         "active slots plus prefill chunks share this "
                         "many tokens per step (must be >= --slots)")
    ap.add_argument("--attn-backend", choices=("gather", "pallas"),
                    default="gather",
                    help="paged-attention decode path: 'gather' (XLA "
                         "gather + dense mask) or 'pallas' (fused flash-"
                         "decoding kernel walking the page table; "
                         "interpret mode on CPU; needs the paged layout)")
    ap.add_argument("--spec-k", type=int, default=0, metavar="K",
                    help="speculative decode: draft up to K tokens per "
                         "slot per step and verify them in the same "
                         "mixed dispatch (0 disables; greedy only, "
                         "needs the mixed step)")
    ap.add_argument("--drafter", choices=("ngram", "model"),
                    default="ngram",
                    help="--spec-k drafter: 'ngram' prompt lookup "
                         "(free, self-speculative) or 'model' (tiny "
                         "greedy draft model; fresh params — "
                         "demonstrates plumbing, drafts at chance)")
    ap.add_argument("--shared-prefix", type=int, default=0, metavar="N",
                    help="prepend the same N-token system prompt to every "
                         "request (demonstrates --prefix-cache sharing)")
    ap.add_argument("--tp", type=int, default=1,
                    help="intra-operator (tensor) parallel degree: shard "
                         "the decode program + KV pool over this many "
                         "devices (serve/parallel.py)")
    ap.add_argument("--dp", type=int, default=1,
                    help="data-parallel replica count: run this many "
                         "engine replicas behind a least-load router")
    ap.add_argument("--serve", action="store_true",
                    help="ONLINE mode: skip the synthetic batch and "
                         "expose the engine over HTTP — POST /generate "
                         "(set \"stream\": true for chunked per-token "
                         "streaming), GET /metrics (Prometheus text: "
                         "TTFT/TPOT percentiles, step latency, pool "
                         "counters), GET /healthz")
    ap.add_argument("--port", type=int, default=8000,
                    help="--serve: TCP port to bind (0 picks a free "
                         "port, printed at startup)")
    ap.add_argument("--host", default="127.0.0.1",
                    help="--serve: bind address")
    ap.add_argument("--watchdog-timeout", type=float, default=30.0,
                    help="--serve: seconds one engine step may run "
                         "before the watchdog logs a flight-recorder "
                         "dump and cancels-and-requeues the active "
                         "slots via the preemption path "
                         "(<= 0 disables the watchdog)")
    ap.add_argument("--trace-level", type=int, choices=(0, 1, 2),
                    default=1,
                    help="tracer detail: 0 off, 1 lifecycle events + "
                         "per-step phase records (default), 2 adds "
                         "per-chunk/per-decode-step events")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome/Perfetto trace_event JSON of "
                         "the run to PATH (batch mode: after the run; "
                         "--serve: on ctrl-c shutdown); load it in "
                         "ui.perfetto.dev or chrome://tracing")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_smoke(args.arch).with_(dtype="float32") if args.smoke \
        else get_config(args.arch)
    n = args.dp * args.tp
    mesh = make_mesh((args.dp, args.tp), ("data", "model"),
                     devices=jax.devices()[:n])
    session = Session(cfg, mesh=mesh)
    spec = None
    if args.spec_k > 0:
        from repro.serve.speculative import SpecConfig
        spec = SpecConfig(k=args.spec_k, drafter=args.drafter)
    serve_kw = dict(tp=args.tp, dp=args.dp,
                    slots=args.slots, max_len=args.max_len,
                    temperature=args.temperature,
                    paged=False if args.dense else None,
                    page_size=args.page_size, kv_pages=args.kv_pages,
                    prefix_cache=args.prefix_cache, lazy=args.lazy,
                    mixed=False if (args.no_mixed or args.dense) else None,
                    chunk_tokens=args.chunk_tokens,
                    attn_backend=args.attn_backend, spec=spec,
                    trace_level=args.trace_level)
    if args.serve:
        wt = args.watchdog_timeout if args.watchdog_timeout > 0 else None
        server = session.serve_http(host=args.host, port=args.port,
                                    watchdog_timeout=wt, **serve_kw)
        print(f"serving {args.arch} on {server.url} "
              f"(POST /generate, GET /metrics, GET /healthz, "
              f"GET /debug/flight, GET /debug/trace; "
              f"watchdog {'off' if wt is None else f'{wt}s'}) "
              f"— ctrl-c to stop", flush=True)
        try:
            while True:
                time.sleep(3600)
        except KeyboardInterrupt:
            if args.trace_out:
                server.driver.export_trace(args.trace_out)
                print(f"trace written to {args.trace_out}", flush=True)
            server.close(drain=False)
        return
    eng = session.serve(**serve_kw)

    rng = np.random.default_rng(0)
    system = rng.integers(0, cfg.vocab_size, size=(args.shared_prefix,))
    for rid in range(args.requests):
        n = int(rng.integers(4, 16))
        frames = (rng.standard_normal((cfg.encoder_ctx, cfg.d_model))
                  .astype(np.float32) if cfg.arch_type == "audio" else None)
        # VLM archs carry synthetic patch embeddings, standing in for
        # the stubbed ViT+projector frontend exactly as frames stand in
        # for the audio mel+conv stack
        images = (rng.standard_normal((cfg.num_image_tokens, cfg.d_model))
                  .astype(np.float32) if cfg.arch_type == "vlm" else None)
        prompt = np.concatenate(
            [system, rng.integers(0, cfg.vocab_size, size=(n,))])
        eng.submit(rid, prompt, max_new=args.max_new, frames=frames,
                   images=images)

    t0 = time.time()
    results = eng.run()
    dt = time.time() - t0
    total = sum(len(r.out) for r in results.values())
    # a dp>1 serve() returns a ReplicaRouter; report its aggregate stats
    # and describe the layout from the first (representative) replica
    rep = eng.engines[0] if hasattr(eng, "engines") else eng
    st = eng.stats
    # trace counters are per-replica: report the worst engine so "1
    # decode trace/replica" states the invariant, not a dp-fold sum
    traces = max(r["decode_traces"] for r in st.get("replicas", [st]))
    layout = f"paged/{rep.page_size}tok-pages" if rep.paged else "dense"
    if getattr(rep, "attn_backend", "gather") != "gather":
        layout += f"+{rep.attn_backend}"
    par = f", tp{rep.tp}" + (f" x dp{eng.dp}" if hasattr(eng, "dp") else "")
    print(f"served {len(results)} requests, {total} tokens "
          f"in {dt:.2f}s ({total / dt:.1f} tok/s, {args.slots} slots{par}, "
          f"{layout} kv {eng.kv_bytes() / 1e6:.1f}MB global / "
          f"{eng.per_device_kv_bytes() / 1e6:.1f}MB per device, "
          f"{st['decode_steps']} decode calls, "
          f"{traces} decode trace/replica)")
    if rep.paged:
        pool = rep.kv_pages * (eng.dp if hasattr(eng, "dp") else 1)
        print(f"  pool: peak {st['peak_pages']}/{pool} pages, "
              f"prefix hit/miss {st['prefix_hit_blocks']}/"
              f"{st['prefix_miss_blocks']} blocks "
              f"(+{st['prefix_tail_hits']} tail), "
              f"{st['preemptions']} preemptions, "
              f"{st['cow_copies']} CoW copies, "
              f"{st['prefix_evictions']} evictions")
    if spec is not None:
        drafted = st.get("spec_drafted", 0)
        accepted = st.get("spec_accepted", 0)
        per_step = ((st["decode_tokens"] - st["prefills"])
                    / max(st.get("decode_slot_steps", 0), 1))
        print(f"  spec: k={args.spec_k} drafter={args.drafter}, "
              f"{accepted}/{drafted} drafts accepted "
              f"({accepted / max(drafted, 1):.2f}), "
              f"{per_step:.2f} accepted tokens/decode step")
    if args.trace_out:
        obj = eng.export_trace(args.trace_out)
        print(f"  trace: {len(obj['traceEvents'])} events written to "
              f"{args.trace_out} (load in ui.perfetto.dev)")
    for rid in sorted(results):
        r = results[rid]
        print(f"  req {rid}{'' if r.done else ' [truncated]'}: {r.out}")


if __name__ == "__main__":
    main()
