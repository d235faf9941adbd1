#!/usr/bin/env python3
"""Show that the serve path runs on a TPU at a registry model's published
widths, and that what it produces is right.

    python3 chip_smoke.py             # one chip: Minitron-4B, bf16
    python3 chip_smoke.py --chips 4   # four chips: Qwen3-14B at tp=4 only

One process, weights drawn from a fixed seed, prompts drawn from the same
seed; nothing is downloaded. The script refuses to run anywhere but a
TPU: it never falls back to the CPU.

One chip (default), two phases, both through ``Session.serve_async`` and
the ``AsyncDriver`` with the engine's defaults (paged pool, mixed step,
``chunk_tokens`` 256):

  * ``serve``  — ``attn_backend="gather"``;
  * ``pallas`` — the same engine and requests with the fused Pallas
    paged-attention kernel, which must be compiled (``tpu_custom_call``
    in the lowered mixed-step program), not interpreted.

``--chips 4`` runs only the sharded phase: Qwen3-14B on a
``("data", "model") = (1, 4)`` mesh, the parameters born sharded, served
at tp=4 with the gather backend.

Every phase submits the same four requests (one prompt of 600 tokens,
which spans three mixed-step chunks, and three of 16-64 tokens), each for
32 new tokens, and checks every generated token against the plain full
forward (``model.forward`` on prompt + output, no cache, no page pool,
no mixed step), teacher-forced: the engine's token must be the
reference's argmax, or its reference logit must lie within
:data:`TOL_ULPS` units in the last place of the config's dtype of the
reference's top logit. Both sides compute in bf16 and round at different
points (chunked prefill, paged attention, and under tp the all-reduces),
so near-ties may resolve either way; a wrong token lies several logits
(over a hundred ulps) below the top.

Earlier lines print what ran and a few smoke timings, which are not
measurements. The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failure exits non-zero before that line is printed.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

SEED = 0
SLOTS = 4
MAX_LEN = 1024
MAX_NEW = 32
PROMPT_LENS = (600, 16, 40, 64)
#: teacher-forced tolerance, in units in the last place of the config's
#: dtype at the magnitude of the reference's top logit: with bf16, 32 ulps
#: is one logit at a top logit of 4 to 8. On a v5e the bf16 engine and
#: the bf16 forward differed by at most 3 ulps (Minitron-4B, one chip)
#: and 9 ulps (Qwen3-14B, 40 layers, tp=4); a random token lies about 5
#: logits below the top
TOL_ULPS = 32
#: the reference forward runs on prompt + output right-padded to this
#: multiple, so the four requests share one compiled program (the model
#: is causal: padding after a position cannot change its logits)
PAD_TO = 128


class SmokeFailure(Exception):
    pass


def make_prompts(vocab_size: int, seed: int = SEED):
    import numpy as np
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab_size, size=(n,)).astype(np.int32)
            for n in PROMPT_LENS]


def make_reference(cfg, ctx=None):
    """``reference(params, tokens, positions, chosen)`` over the plain
    full forward, jitted: for each of ``positions`` (those whose next
    token the engine generated) the reference argmax, the reference's top
    logit and its logit at the engine's token ``chosen``. ``ctx`` is a
    context (the mesh's sharding rules) to run it under."""
    import contextlib

    import jax
    import jax.numpy as jnp

    from repro.models import get_model

    model = get_model(cfg)

    @jax.jit
    def scores(params, tokens, positions, chosen):
        logits, _ = model.forward(params, {"tokens": tokens}, cfg)
        rows = logits[0, positions].astype(jnp.float32)      # (n, V)
        got = jnp.take_along_axis(rows, chosen[:, None], axis=1)[:, 0]
        return rows.argmax(-1), rows.max(-1), got

    def run(*args):
        with (ctx() if ctx is not None else contextlib.nullcontext()):
            return scores(*args)

    return run


def ulp(x, dtype):
    """One unit in the last place of ``dtype`` at magnitude ``|x|``."""
    import numpy as np
    import jax.numpy as jnp
    eps = float(jnp.finfo(jnp.dtype(dtype)).eps)
    mag = np.maximum(np.abs(np.asarray(x, np.float64)),
                     np.finfo(np.float32).tiny)
    return eps * np.exp2(np.floor(np.log2(mag)))


def teacher_forced_check(cfg, params, prompt, out, reference,
                         tol_ulps: float = TOL_ULPS) -> dict:
    """Check every generated token of one request against the plain full
    forward. Returns counts and the worst gap (in logits and in ulps);
    ``ok`` is False when any token lies beyond the tolerance."""
    import numpy as np

    prompt = np.asarray(prompt, np.int32)
    out = np.asarray(out, np.int32)
    seq = np.concatenate([prompt, out[:-1]])
    length = -(-len(seq) // PAD_TO) * PAD_TO
    tokens = np.zeros((1, length), np.int32)
    tokens[0, :len(seq)] = seq
    positions = np.arange(len(prompt) - 1, len(seq), dtype=np.int32)
    argmax, top, got = (np.asarray(a) for a in reference(
        params, tokens, positions, out))
    gap = top - got
    gap_ulps = gap / ulp(top, cfg.dtype)
    bad = np.flatnonzero(gap_ulps > tol_ulps)
    return {"n": int(len(out)), "exact": int((argmax == out).sum()),
            "max_gap": float(gap.max()),
            "max_gap_ulps": float(gap_ulps.max()),
            "bad": [int(i) for i in bad], "ok": bool(len(bad) == 0)}


def _wait(streams, errors, timeout: float):
    """Every stream's Request record; raises if the driver's loop thread
    died (its exception is in ``errors``) or ``timeout`` passes."""
    deadline = time.monotonic() + timeout
    records = []
    for stream in streams:
        while True:
            if errors:
                raise SmokeFailure(f"the serve loop raised: {errors[0]!r}")
            try:
                records.append(stream.result(timeout=1.0))
                break
            except TimeoutError:
                if time.monotonic() > deadline:
                    raise SmokeFailure(
                        f"request {stream.rid} not done after {timeout}s")
    return records


def mixed_step_text(engine) -> str:
    """The engine's mixed-step program, lowered at its own shapes."""
    import jax
    import jax.numpy as jnp

    t = engine.chunk_tokens
    col = lambda dt: jax.ShapeDtypeStruct((t,), dt)     # noqa: E731
    with engine._ctx():
        lowered = engine._mixed.lower(
            engine.params, engine._cache,
            jax.ShapeDtypeStruct((t, 1), jnp.int32), col(jnp.int32),
            col(jnp.int32), col(jnp.bool_), col(jnp.bool_), None)
    return lowered.as_text()


def serve_phase(name, session, prompts, reference, errors, **serve_kw):
    """Serve the requests through the AsyncDriver, check the streams and
    every token against the reference; returns the outputs."""
    import jax

    driver = session.serve_async(slots=SLOTS, max_len=MAX_LEN, start=False,
                                 **serve_kw)
    engine = driver.engine
    streams = [driver.submit(p, max_new=MAX_NEW) for p in prompts]
    t0 = time.perf_counter()
    driver.start()
    try:
        records = _wait(streams, errors, timeout=900.0)
    finally:
        driver.stop(drain=False)
    wall = time.perf_counter() - t0
    outs = [list(r.out) for r in records]
    for r, stream in zip(records, streams):
        if not r.done or len(r.out) != MAX_NEW or stream.emitted != MAX_NEW:
            raise SmokeFailure(
                f"{name}: request {r.rid} done={r.done} with {len(r.out)} "
                f"tokens ({stream.emitted} streamed), want {MAX_NEW}")
    print(f"[{name}] {len(records)} requests done, "
          f"{[len(o) for o in outs]} tokens each", flush=True)

    steps = [s for s in engine.tracer.flight()["steps"]
             if "dispatch" in s["phases"]]
    first, rest = steps[0], steps[1:]
    print(f"[{name}] decode_traces {engine.stats['decode_traces']}, "
          f"{len(steps)} device steps; first step {first['dur']:.2f}s "
          f"(compile included); smoke timing, not a metric: "
          f"median step {statistics.median(s['dur'] for s in rest):.4f}s, "
          f"wall {wall:.2f}s", flush=True)

    beyond = {}
    for i, (p, o) in enumerate(zip(prompts, outs)):
        res = teacher_forced_check(session.cfg, session.params, p, o,
                                   reference)
        print(f"[{name}] request {i} (prompt {len(p)}): {res['exact']}/"
              f"{res['n']} tokens equal the reference argmax, worst gap "
              f"{res['max_gap']:.4g} logits = {res['max_gap_ulps']:.3g} "
              f"ulps (tolerance {TOL_ULPS} ulps)", flush=True)
        if not res["ok"]:
            beyond[i] = res["bad"]
    if beyond:
        raise SmokeFailure(
            f"{name}: tokens beyond {TOL_ULPS} ulps of the reference's top "
            f"logit (request: positions) {beyond}")

    text = mixed_step_text(engine)
    kernel = "tpu_custom_call" in text
    print(f"[{name}] tpu_custom_call in the mixed step: {kernel}",
          flush=True)
    if kernel != (serve_kw.get("attn_backend") == "pallas"):
        raise SmokeFailure(
            f"{name}: the Pallas kernel is "
            f"{'present' if kernel else 'absent'} in the compiled mixed "
            f"step (interpreted or misrouted)")
    devices = {d for x in jax.tree.leaves(engine._cache)
               for d in x.sharding.device_set}
    for d in sorted(devices, key=lambda d: d.id):
        stats = memory(d)
        print(f"[{name}] device {d.id}: bytes_in_use "
              f"{stats.get('bytes_in_use')}, peak_bytes_in_use "
              f"{stats.get('peak_bytes_in_use')}, kv pool "
              f"{engine.per_device_kv_bytes()}", flush=True)
    return outs


def memory(device) -> dict:
    """``device.memory_stats()`` ({} where the backend reports none)."""
    return device.memory_stats() or {}


def param_bytes_per_device(params):
    import jax
    per = {}
    for leaf in jax.tree.leaves(params):
        for shard in leaf.addressable_shards:
            per[shard.device.id] = per.get(shard.device.id, 0) \
                + shard.data.nbytes
    return per


def one_chip(errors):
    import jax

    from repro.api import Session
    from repro.configs import get_config
    from repro.launch.mesh import make_mesh

    cfg = get_config("minitron-4b")
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    session = Session(cfg, mesh=mesh, seed=SEED)
    t0 = time.perf_counter()
    params = jax.block_until_ready(session.params)
    nbytes = sum(x.nbytes for x in jax.tree.leaves(params))
    print(f"model {cfg.name}: {cfg.num_layers} layers, d_model "
          f"{cfg.d_model}, heads {cfg.num_heads}/{cfg.num_kv_heads}, "
          f"vocab {cfg.vocab_size}, {cfg.dtype}; {cfg.param_count()} "
          f"parameters, {nbytes} bytes; init {time.perf_counter() - t0:.1f}s "
          f"(compile included), peak_bytes_in_use "
          f"{memory(jax.devices()[0]).get('peak_bytes_in_use')}",
          flush=True)
    prompts = make_prompts(cfg.vocab_size)
    reference = make_reference(cfg)
    gather = serve_phase("serve", session, prompts, reference, errors,
                         attn_backend="gather")
    pallas = serve_phase("pallas", session, prompts, reference, errors,
                         attn_backend="pallas")
    same = sum(a == b for x, y in zip(pallas, gather) for a, b in zip(x, y))
    print(f"[pallas] tokens equal the gather phase's: {pallas == gather} "
          f"({same}/{SLOTS * MAX_NEW})", flush=True)


def four_chips(errors):
    import jax

    from repro.api import Session
    from repro.configs import get_config
    from repro.core.pspec import sharding_rules
    from repro.launch.mesh import make_mesh

    if len(jax.devices()) < 4:
        raise SmokeFailure(f"--chips 4 needs 4 devices, JAX sees "
                           f"{len(jax.devices())}")
    cfg = get_config("qwen3-14b")
    mesh = make_mesh((1, 4), ("data", "model"), devices=jax.devices()[:4])
    session = Session(cfg, mesh=mesh, seed=SEED)
    t0 = time.perf_counter()
    params = jax.block_until_ready(session.params)
    total = sum(x.nbytes for x in jax.tree.leaves(params))
    per = param_bytes_per_device(params)
    print(f"model {cfg.name}: {cfg.num_layers} layers, d_model "
          f"{cfg.d_model}, heads {cfg.num_heads}/{cfg.num_kv_heads}, "
          f"vocab {cfg.vocab_size}, {cfg.dtype}; {cfg.param_count()} "
          f"parameters, {total} bytes; init {time.perf_counter() - t0:.1f}s "
          f"(compile included)", flush=True)
    for dev, b in sorted(per.items()):
        print(f"  device {dev}: parameter bytes {b} "
              f"({b / total:.3f} of the whole)", flush=True)
        if b > 0.35 * total:
            raise SmokeFailure(f"device {dev} holds {b / total:.2f} of the "
                               "parameters; tp=4 should hold about 1/4")
    rules = session.strategy.rules(mesh)
    reference = make_reference(cfg, ctx=lambda: sharding_rules(mesh, rules))
    prompts = make_prompts(cfg.vocab_size)
    serve_phase("tp4", session, prompts, reference, errors, tp=4, dp=1)
    in_use = {d.id: memory(d).get("bytes_in_use", 0)
              for d in jax.devices()[:4]}
    if max(in_use.values()) > 0.5 * total:
        raise SmokeFailure(f"bytes in use per device {in_use}: one device "
                           "holds more than half of the model")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1 (default): Minitron-4B, gather and pallas "
                         "phases; 4: only Qwen3-14B at tp=4")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    print(f"jax {jax.__version__}: platform {platform}, device_kind "
          f"{devices[0].device_kind}, {len(devices)} devices", flush=True)
    if platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {platform}); this check runs "
              "only on the chip", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository's src/ is not beside this "
              f"script ({e})", file=sys.stderr)
        return 2
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    errors = []

    def hook(exc):
        errors.append(exc.exc_value)
        threading.__excepthook__(exc)

    threading.excepthook = hook
    t0 = time.perf_counter()
    try:
        if args.chips == 1:
            one_chip(errors)
        else:
            four_chips(errors)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    if errors:
        print(f"chip_smoke: FAILED: a thread raised {errors[0]!r}",
              file=sys.stderr)
        return 1
    print(f"passed in {time.perf_counter() - t0:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
