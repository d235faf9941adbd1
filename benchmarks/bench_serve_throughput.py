"""Serve-engine throughput: tokens/s vs. slot count on a tiny config.

The point of the batched slot-table decode is that one engine step costs
ONE device program regardless of occupancy, so tokens/s should GROW with
the slot count on a fixed request workload (the per-slot-dispatch engine
it replaced was flat). Each slot count serves the same workload twice and
times the second pass, so compile/trace time is excluded.

Each row also reports ``peak_kv_bytes`` — the engine's resident decode
state. The dense layout grows it linearly in slots (slots * max_len rows
whether or not requests are short); the paged layout (--paged) holds one
shared page pool, sizable via --kv-pages independently of the slot count,
which is the fragmentation win the paged tests pin down.

``--tp`` / ``--dp`` serve the same workload through the sharded paths
(serve/parallel.py): tp shards the one-trace decode program + KV pool
over that many devices, dp replicates engines behind the least-load
router; ``--parallel-sweep`` crosses tp in {1,2,4} x dp in {1,2} and
reports tokens/s plus per-device peak KV bytes per cell (the acceptance
signal: per-device KV ~ 1/tp of the unsharded pool, one decode trace per
replica throughout). Under ``JAX_PLATFORMS=cpu`` any of the three
forces 8 virtual host devices before jax initializes; override via
XLA_FLAGS.

``--mixed-workload`` runs the chunked-prefill comparison instead: a
long/short-interleaved prompt mix on the paged layout, each slot count
served once with the legacy split prefill/decode path (``mixed=False``)
and once with the unified mixed token-slot step (``--chunk-tokens``
budget) — the rows pin TTFT p50/p90/p99 with chunking off vs. on and
the mixed path's bounded trace count (the CI ``mixed-batch-smoke`` job
asserts both).

CLI (JSON output, used by the CI smoke steps):

    PYTHONPATH=src:. python benchmarks/bench_serve_throughput.py \
        --slots 1 2 4 --requests 8 --max-new 8 --json out.json
"""
from __future__ import annotations

import os
import sys

if os.environ.get("JAX_PLATFORMS") == "cpu" and \
        any(a.startswith(("--tp", "--dp", "--parallel-sweep"))
            for a in sys.argv):
    # must land before jax (imported below via repro.models) initializes
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")

import argparse
import hashlib
import json
import time

import numpy as np

from repro.configs.base import ModelConfig
from repro.models import get_model
from repro.serve.driver import AsyncDriver
from repro.serve.engine import ServeEngine
from repro.serve.parallel import ReplicaRouter, replica_meshes

TINY = ModelConfig(name="bench-serve", arch_type="dense", num_layers=2,
                   d_model=128, num_heads=4, num_kv_heads=4, d_ff=256,
                   vocab_size=256, dtype="float32")


def _workload(rng, n_requests, mixed: bool = False,
              repetitive: bool = False):
    """Uniform short prompts by default; ``mixed=True`` interleaves LONG
    (40-56 token) and short (4-8) prompts — the chunked-prefill stress
    mix, where a long admission stalls every decoding slot unless
    prefill is chunked into the step budget. ``repetitive=True`` tiles a
    short random motif into each prompt — the prompt-lookup drafter's
    best case (templated/looping text), and the tiny model's greedy
    continuation of a periodic context quickly enters its own cycle, so
    the n-gram drafter keeps matching and ``--spec-k`` rows show > 1
    accepted token per decode step."""
    if repetitive:
        prompts = []
        for _ in range(n_requests):
            motif = rng.integers(0, TINY.vocab_size,
                                 size=(int(rng.integers(3, 6)),))
            reps = int(rng.integers(4, 7))
            prompts.append(np.tile(motif, reps).astype(np.int32))
        return prompts
    if mixed:
        return [rng.integers(
            0, TINY.vocab_size,
            size=(int(rng.integers(40, 57) if i % 2 == 0
                      else rng.integers(4, 9)),)).astype(np.int32)
            for i in range(n_requests)]
    return [rng.integers(0, TINY.vocab_size,
                         size=(int(rng.integers(4, 13)),)).astype(np.int32)
            for _ in range(n_requests)]


def bench(params, *, slots: int, n_requests: int, max_new: int,
          max_len: int = 64, seed: int = 0, paged: bool = False,
          page_size: int = 16, kv_pages=None, prefix_cache: bool = False,
          lazy: bool = False, tp: int = 1, dp: int = 1,
          mixed=None, chunk_tokens=None, mixed_workload: bool = False,
          attn_backend: str = "gather", spec_k: int = 0,
          drafter: str = "ngram", repetitive: bool = False,
          trace_level: int = 1, trace_out=None) -> dict:
    kw = dict(slots=slots, max_len=max_len, paged=paged,
              page_size=page_size, kv_pages=kv_pages,
              prefix_cache=prefix_cache, lazy=lazy,
              attn_backend=attn_backend, trace_level=trace_level)
    if mixed is not None:
        kw["mixed"] = mixed
    if chunk_tokens is not None:
        kw["chunk_tokens"] = chunk_tokens
    if spec_k > 0:
        from repro.serve.speculative import SpecConfig
        kw["spec"] = SpecConfig(k=spec_k, drafter=drafter)
        kw.setdefault("chunk_tokens", max(256, slots * (spec_k + 1)))
    if dp > 1:
        eng = ReplicaRouter(TINY, params, dp=dp, tp=tp, **kw)
    elif tp > 1:
        [mesh] = replica_meshes(1, tp)
        eng = ServeEngine(TINY, params, mesh=mesh, **kw)
    else:
        eng = ServeEngine(TINY, params, **kw)
    rng = np.random.default_rng(seed)
    prompts = _workload(rng, n_requests, mixed=mixed_workload,
                        repetitive=repetitive)

    # warm pass (batch run): traces decode + every prefill bucket
    for i, p in enumerate(prompts):
        eng.submit(i, p, max_new=max_new)
    eng.run()
    # steady state: counters restart at zero, trace counters stay
    # monotonic so the one-trace CI assertion still covers BOTH passes
    eng.reset_stats()
    # measured pass through the AsyncDriver: deferred start means the
    # whole batch admits exactly like run() (same decode_steps), while
    # per-request TTFT/TPOT percentiles ride along for free
    drv = AsyncDriver(eng, start=False)
    streams = [drv.submit(p, max_new=max_new, rid=n_requests + i)
               for i, p in enumerate(prompts)]
    t0 = time.perf_counter()
    drv.start()
    drv.join(timeout=600.0)
    dt = time.perf_counter() - t0
    drv.stop(drain=False)
    outs = {s.rid: list(s.result(timeout=0.0).out) for s in streams}
    toks = sum(len(o) for o in outs.values())
    # greedy-token fingerprint of the measured pass: rows from different
    # backends (gather vs pallas) over the same workload must match it
    # exactly — the CI paged-kernel-smoke identity check
    digest = hashlib.sha1(json.dumps(
        [outs[r] for r in sorted(outs)]).encode()).hexdigest()[:16]
    lat = drv.metrics.latency_summary()
    st = eng.stats
    # trace counters are a PER-REPLICA property: report the worst replica
    # so "decode_traces == 1" means one trace in EVERY engine
    reps = st.get("replicas", [st])
    rep0 = eng.engines[0] if dp > 1 else eng
    # span coverage: phase laps over step wall time (the >= 0.95
    # acceptance bar); export covers BOTH passes — the tracer is not
    # reset with the counters, which is exactly what an operator wants
    from repro.serve.tracing import phase_coverage
    tracers = eng.tracers if hasattr(eng, "tracers") else [eng.tracer]
    coverage = round(phase_coverage(tracers), 4)
    if trace_out:
        eng.export_trace(trace_out)
    return {
        "slots": slots,
        "tp": tp,
        "dp": dp,
        "mixed": bool(getattr(rep0, "mixed", False)),
        "chunk_tokens": int(getattr(rep0, "chunk_tokens", 0)),
        "spec_k": spec_k,
        "drafter": drafter if spec_k > 0 else "",
        "spec_drafted": st.get("spec_drafted", 0),
        "spec_accepted": st.get("spec_accepted", 0),
        "spec_accept_rate": round(
            st.get("spec_accepted", 0) / max(st.get("spec_drafted", 0), 1),
            4),
        # decode tokens per (step, decoding slot) pair, prefill-sampled
        # firsts excluded: exactly 1.0 without speculation regardless of
        # occupancy, in (1, k+1] when drafts land
        "accepted_tokens_per_step": round(
            (st["decode_tokens"] - st["prefills"])
            / max(st.get("decode_slot_steps", 0), 1), 4),
        "requests": n_requests,
        "tokens": toks,
        "wall_s": round(dt, 4),
        "tokens_per_s": round(toks / dt, 1),
        "decode_steps": st["decode_steps"],
        "decode_traces": max(r["decode_traces"] for r in reps),
        "prefill_traces": max(r["prefill_traces"] for r in reps),
        "prefill_chunk_tokens": st.get("prefill_chunk_tokens", 0),
        "paged": rep0.paged,
        "attn_backend": getattr(rep0, "attn_backend", "gather"),
        "trace_level": trace_level,
        "trace_phase_coverage": coverage,
        "out_digest": digest,
        "peak_kv_bytes": eng.kv_bytes(),
        "per_device_peak_kv_bytes": eng.per_device_kv_bytes(),
        # request latency percentiles (seconds, from the driver metrics)
        **{k: round(v, 6) for k, v in lat.items()},
        # pool telemetry (zeros on the dense layout / with sharing off)
        "pages_in_use": st["pages_in_use"],
        "peak_pages": st["peak_pages"],
        "prefix_hit_blocks": st["prefix_hit_blocks"],
        "prefix_miss_blocks": st["prefix_miss_blocks"],
        "preemptions": st["preemptions"],
        "cow_copies": st["cow_copies"],
    }


def run() -> list:
    """Harness entry (benchmarks/run.py CSV convention)."""
    params = get_model(TINY).init(__import__("jax").random.key(0), TINY)
    rows = []
    for paged in (False, True):
        for slots in (1, 2, 4, 8):
            r = bench(params, slots=slots, n_requests=8, max_new=8,
                      paged=paged)
            layout = "paged" if paged else "dense"
            rows.append({
                "name": f"serve/throughput_{layout}_slots{slots}",
                "us_per_call": round(
                    1e6 * r["wall_s"] / max(r["decode_steps"], 1), 1),
                "derived": (f"tok_per_s={r['tokens_per_s']} "
                            f"decode_steps={r['decode_steps']} "
                            f"decode_traces={r['decode_traces']} "
                            f"peak_kv_bytes={r['peak_kv_bytes']}"),
            })
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", type=int, nargs="+", default=[1, 2, 4, 8])
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--paged", action="store_true",
                    help="use the paged (block-table) KV layout")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--kv-pages", type=int, default=None,
                    help="paged pool size (default: dense-capacity parity)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree (shard the decode "
                         "program + KV pool; forces 8 host devices "
                         "under JAX_PLATFORMS=cpu)")
    ap.add_argument("--dp", type=int, default=1,
                    help="data-parallel replica count (least-load router)")
    ap.add_argument("--parallel-sweep", action="store_true",
                    help="sweep tp in {1,2,4} x dp in {1,2} on the paged "
                         "layout at the first --slots value")
    ap.add_argument("--mixed-workload", action="store_true",
                    help="chunked-prefill comparison: long/short prompt "
                         "mix on the paged layout, each slot count run "
                         "with mixed stepping OFF then ON")
    ap.add_argument("--chunk-tokens", type=int, default=None,
                    help="mixed-step token budget (engine default 256)")
    ap.add_argument("--attn-backend", choices=("gather", "pallas"),
                    default="gather",
                    help="paged-attention decode path (pallas = fused "
                         "flash-decoding kernel, interpret mode on CPU; "
                         "implies --paged); rows carry the backend and "
                         "an out_digest column so gather-vs-pallas runs "
                         "can be diffed for token identity")
    ap.add_argument("--spec-k", type=int, default=0, metavar="K",
                    help="speculative decode: draft up to K tokens per "
                         "slot per step, verified in the same mixed "
                         "dispatch (0 disables; implies --paged; rows "
                         "gain spec_accept_rate and "
                         "accepted_tokens_per_step columns, and "
                         "out_digest must equal the spec-off run's — "
                         "the CI speculative-smoke identity check)")
    ap.add_argument("--drafter", choices=("ngram", "model"),
                    default="ngram",
                    help="--spec-k drafter: 'ngram' prompt lookup or "
                         "'model' (tiny fresh-params draft model)")
    ap.add_argument("--repetitive", action="store_true",
                    help="tile short random motifs into every prompt — "
                         "the prompt-lookup drafter's best case; the "
                         "workload the speculative-smoke job drives")
    ap.add_argument("--trace-level", type=int, choices=(0, 1, 2),
                    default=1,
                    help="engine tracer detail: 0 off, 1 lifecycle + "
                         "phase records (default), 2 per-chunk detail; "
                         "rows carry trace_phase_coverage (phase laps "
                         "over step wall time)")
    ap.add_argument("--trace-out", type=str, default="", metavar="PATH",
                    help="write the LAST bench row's Chrome/Perfetto "
                         "trace_event JSON to PATH (with "
                         "--mixed-workload that is the mixed-mode row "
                         "at the largest slot count)")
    ap.add_argument("--json", type=str, default="",
                    help="write results to this path (default: stdout)")
    args = ap.parse_args()

    import jax
    params = get_model(TINY).init(jax.random.key(0), TINY)
    if args.parallel_sweep:
        results = [bench(params, slots=args.slots[0],
                         n_requests=args.requests, max_new=args.max_new,
                         max_len=args.max_len, paged=True,
                         page_size=args.page_size, kv_pages=args.kv_pages,
                         tp=tp, dp=dp, trace_level=args.trace_level,
                         trace_out=args.trace_out or None)
                   for tp in (1, 2, 4) for dp in (1, 2)
                   if tp * dp <= jax.device_count()]
    elif args.mixed_workload:
        # spec rides on the mixed step only, so the split (mixed=False)
        # baseline rows always run spec-off; the mixed rows carry
        # --spec-k so the long/short mix reports accept rate and
        # accepted tokens/step next to TTFT
        results = [bench(params, slots=s, n_requests=args.requests,
                         max_new=args.max_new, max_len=args.max_len,
                         paged=True, page_size=args.page_size,
                         kv_pages=args.kv_pages, mixed=mixed,
                         chunk_tokens=args.chunk_tokens,
                         mixed_workload=True,
                         spec_k=args.spec_k if mixed else 0,
                         drafter=args.drafter,
                         trace_level=args.trace_level,
                         trace_out=args.trace_out or None)
                   for s in args.slots for mixed in (False, True)]
    else:
        results = [bench(params, slots=s, n_requests=args.requests,
                         max_new=args.max_new, max_len=args.max_len,
                         paged=(args.paged or args.tp > 1 or args.dp > 1
                                or args.attn_backend == "pallas"
                                or args.spec_k > 0),
                         page_size=args.page_size, kv_pages=args.kv_pages,
                         tp=args.tp, dp=args.dp,
                         attn_backend=args.attn_backend,
                         spec_k=args.spec_k, drafter=args.drafter,
                         repetitive=args.repetitive,
                         trace_level=args.trace_level,
                         trace_out=args.trace_out or None)
                   for s in args.slots]
    report = {"config": TINY.name, "results": results}
    out = json.dumps(report, indent=2)
    if args.json:
        with open(args.json, "w") as f:
            f.write(out + "\n")
        base = results[0]["tokens_per_s"]
        for r in results:
            mode = " mixed" if r["mixed"] else " split"
            if r["spec_k"]:
                mode += (f" spec{r['spec_k']}/{r['drafter']} "
                         f"acc={r['spec_accept_rate']:.2f} "
                         f"tok/step={r['accepted_tokens_per_step']:.2f}")
            print(f"slots={r['slots']:>2} tp{r['tp']} dp{r['dp']}{mode} "
                  f"{r['tokens_per_s']:>8.1f} tok/s "
                  f"({r['tokens_per_s'] / base:.2f}x, "
                  f"{r['decode_steps']} decode calls, "
                  f"{r['decode_traces']} trace/replica, "
                  f"kv {r['peak_kv_bytes'] / 1e6:.2f}MB global / "
                  f"{r['per_device_peak_kv_bytes'] / 1e6:.2f}MB per dev) "
                  f"ttft p50/p90/p99 {r['ttft_p50_s'] * 1e3:.1f}/"
                  f"{r['ttft_p90_s'] * 1e3:.1f}/"
                  f"{r['ttft_p99_s'] * 1e3:.1f}ms "
                  f"tpot {r['tpot_p50_s'] * 1e3:.2f}/"
                  f"{r['tpot_p90_s'] * 1e3:.2f}/"
                  f"{r['tpot_p99_s'] * 1e3:.2f}ms")
    else:
        print(out)


if __name__ == "__main__":
    main()
