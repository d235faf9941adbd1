"""One run of one cell: set up, serve the cell's traffic, measure a
window, check what was served against the plain reference, report.

Everything that belongs to one cell is found by name:

  BENCHMARK.json          the cell: its configuration, traffic and chips
  configs/<config>.json   the model, its mesh and the engine's sizing
  mixes/<traffic>.json    the traffic's shape (traffic.py reads it)
  cells/<cell>.json       the cell's load (rate or clients) and the limit
                          of its correctness check
  layer_metrics/<m>.py    one reader per per-layer metric

The engine runs with its own defaults for every serve option the
configuration does not size. Times are ``time.perf_counter()`` seconds,
the clock the engine's tracer stamps its spans with.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import random
import sys
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path
from types import SimpleNamespace
from typing import List, Optional

import numpy as np

import flops
import peaks as peaks_table
import traffic

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

#: served tokens the correctness sample aims for, and the fewest a run
#: may compare
CHECK_TOKENS = 300
CHECK_MIN_TOKENS = 100
#: how long the primer (the first request, which compiles the step
#: program on a cold cache) may take
PRIME_TIMEOUT_S = 1100.0
#: how long a closed loop's clients may take to fill every slot
FILL_TIMEOUT_S = 120.0
#: per-layer metrics of a traced run read at most this much of the window
TRACE_WINDOW_S = 10.0


class NoChip(Exception):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


class RunFailed(Exception):
    """The run could not produce a result."""


def log(*a):
    print(*a, file=sys.stderr, flush=True)


# ------------------------------------------------------------ the files
def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell_files(name: str) -> SimpleNamespace:
    """The cell's entry and every file it names."""
    bench = benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return SimpleNamespace(
        bench=bench, workload=w, config=load_json(ROOT / conf["file"]),
        mix=load_json(HERE / "mixes" / f"{w['traffic']}.json"),
        load=load_json(HERE / "cells" / f"{name}.json"))


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def check_platform(chips: int):
    """The devices the cell runs on; NoChip unless they are TPUs and
    there are enough of them."""
    import jax
    devices = jax.devices()
    log(f"jax {jax.__version__}: platform {devices[0].platform}, "
        f"device_kind {devices[0].device_kind}, {len(devices)} devices")
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees "
                     f"{len(devices)}")
    return devices[:chips]


#: configuration-file key -> ModelConfig field
_FIELDS = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
           "num_attention_heads": "num_heads",
           "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
           "intermediate_size": "d_ff", "vocab_size": "vocab_size",
           "max_position_embeddings": "max_seq_len",
           "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
           "qk_norm": "qk_norm", "tie_word_embeddings": "tie_embeddings",
           "torch_dtype": "dtype"}


def model_config(c: dict):
    """The program's ModelConfig of a dense decoder at every size the
    configuration file states; refused unless the file describes the one
    block the benchmark's reference computes (SwiGLU, no biases)."""
    from repro.configs import ModelConfig
    if c.get("hidden_act") != "silu" or c.get("attention_bias", False):
        raise RunFailed(f"{c['name']}: the reference computes a gated silu "
                        f"MLP without attention biases")
    return ModelConfig(name=c["name"], arch_type="dense", source=c["source"],
                       **{f: c[k] for k, f in _FIELDS.items()})


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    when set, else the fixed ``.jax_cache/`` at the checkout's root.
    Every program is cached, however quickly it compiled."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def process_start() -> float:
    """``perf_counter`` time at which this process started (Linux)."""
    try:
        with open("/proc/self/stat") as f:
            ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.perf_counter() - (uptime - ticks
                                      / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return START


START = time.perf_counter()


# ------------------------------------------------------------ the client
@dataclass
class Sent:
    """One request as the client saw it."""
    req: traffic.Req
    due: float                       # perf_counter time it was due
    sent: float = 0.0                # when its client thread started
    submitted: Optional[float] = None  # when driver.submit returned
    rid: Optional[int] = None
    times: List[float] = field(default_factory=list)
    tokens: List[int] = field(default_factory=list)
    done: bool = False
    error: Optional[str] = None
    finished: Optional[float] = None


class Compiles:
    """Counts the traces and compiles JAX reports, with their times (one
    listener per process)."""

    NAMES = ("/jax/core/compile/jaxpr_trace_duration",
             "/jax/core/compile/backend_compile_duration")
    events: List[float] = []
    _registered = False

    def __init__(self):
        import jax
        if not Compiles._registered:
            jax.monitoring.register_event_duration_secs_listener(
                Compiles._on)
            Compiles._registered = True

    @staticmethod
    def _on(name, secs, **_):
        if name in Compiles.NAMES:
            Compiles.events.append(time.perf_counter())

    def between(self, t0, t1) -> int:
        return sum(t0 <= t < t1 for t in self.events)


def consume(driver, s: Sent):
    """Submit ``s`` and stamp every token as it reaches the client."""
    import jax
    try:
        with jax.profiler.TraceAnnotation("bench.submit"):
            stream = driver.submit(s.req.prompt, max_new=s.req.max_new)
        s.submitted = time.perf_counter()
        s.rid = stream.rid
        for tok in stream:
            s.times.append(time.perf_counter())
            s.tokens.append(tok)
        rec = stream.result(timeout=1.0)
        s.done = bool(getattr(rec, "done", False))
    except Exception as e:                      # noqa: BLE001
        s.error = repr(e)
    s.finished = time.perf_counter()


class OpenLoop:
    """Sends every request at its due time from a thread of its own."""

    def __init__(self, driver, reqs, origin):
        self.driver = driver
        self.sent = [Sent(r, origin + r.due) for r in reqs]
        self.threads: List[threading.Thread] = []
        self._stop = threading.Event()
        self.sched = threading.Thread(target=self._run, name="bench-sched",
                                      daemon=True)

    def start(self):
        self.sched.start()

    def _run(self):
        for s in self.sent:
            wait = s.due - time.perf_counter()
            if wait > 0 and self._stop.wait(wait):
                return
            s.sent = time.perf_counter()
            t = threading.Thread(target=consume, args=(self.driver, s),
                                 daemon=True)
            t.start()
            self.threads.append(t)

    def stop(self):
        self._stop.set()
        self.sched.join()

    def join(self, timeout):
        deadline = time.perf_counter() + timeout
        for t in self.threads:
            t.join(max(0.0, deadline - time.perf_counter()))


class ClosedLoop:
    """``len(lists)`` clients, each sending its next request when its
    last one completes. A request is in ``sent`` from the moment it is
    sent, so one that outlasts the window is counted in it."""

    def __init__(self, driver, lists):
        self.driver = driver
        self.lists = lists
        self.sent: List[Sent] = []
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self.threads = [threading.Thread(target=self._client, args=(c,),
                                         name=f"bench-client-{c}",
                                         daemon=True)
                        for c in range(len(lists))]

    def start(self):
        for t in self.threads:
            t.start()

    def _client(self, c):
        i = 0
        while not self._stop.is_set():
            req = self.lists[c][i % len(self.lists[c])]
            now = time.perf_counter()
            s = Sent(req, now, sent=now)
            with self._lock:
                self.sent.append(s)
            consume(self.driver, s)
            if s.error is not None and self._stop.is_set():
                with self._lock:     # sent as the loop was being stopped
                    self.sent.remove(s)
                return
            if s.error is not None:
                return
            i += 1

    def wait_filled(self, timeout: float) -> float:
        """Block until every client's first request has its first token
        (every slot decoding); returns when that was. The clients'
        ``driver.submit`` calls can wait seconds for the step loop's
        lock, so a fixed warm-up would open the window on half-empty
        slots in some runs."""
        deadline = time.perf_counter() + timeout
        n = len(self.lists)
        while time.perf_counter() < deadline:
            with self._lock:
                first = {}
                for s in self.sent:
                    first.setdefault(s.req.client, s)
            if len(first) == n and all(s.times for s in first.values()):
                return max(s.times[0] for s in first.values())
            time.sleep(0.01)
        raise RunFailed(f"the {n} clients' first requests had no first "
                        f"token {timeout:.0f} s after they were sent")

    def stop(self):
        self._stop.set()

    def join(self, timeout):
        deadline = time.perf_counter() + timeout
        for t in self.threads:
            t.join(max(0.0, deadline - time.perf_counter()))


# --------------------------------------------------------- end to end
def ttft(s: Sent, limit: float, t_open: float) -> float:
    """Seconds from due to the first token. A request that failed, or
    had none by ``limit``, reads ``limit - t_open`` and a second more:
    above the latency of any request due at ``t_open`` or later."""
    if s.error is not None or not s.times or s.times[0] > limit:
        return limit - t_open + 1.0
    return s.times[0] - s.due


def window_gaps(sent, t0, t1):
    """Every inter-token gap at the client whose later token arrived in
    ``[t0, t1)``."""
    return [b - a for s in sent for a, b in zip(s.times, s.times[1:])
            if t0 <= b < t1]


def end_to_end(name, run) -> Optional[float]:
    if name == "setup_s":
        return run.setup_s
    if name == "itl_p95_ms":
        return 1e3 * traffic.percentile(
            window_gaps(run.sent, run.t_open, run.t_close), 0.95)
    if name == "output_tok_per_s":
        n = sum(run.t_open <= t < run.t_close for s in run.sent
                for t in s.times)
        return n / (run.t_close - run.t_open)
    raise KeyError(f"no definition of end-to-end metric {name!r}")


def layer_metric(name, run) -> Optional[float]:
    path = HERE / "layer_metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"layer_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


# ---------------------------------------------------------- correctness
def check_sample(candidates, seed, target=CHECK_TOKENS):
    """The longest served request, then others drawn from the seed
    until ``target`` served tokens are in the sample."""
    pool = sorted(candidates, key=lambda s: (-len(s.tokens), s.req.idx))
    if not pool:
        return []
    sample, rest = [pool[0]], pool[1:]
    random.Random(seed).shuffle(rest)
    n = len(pool[0].tokens)
    for s in rest:
        if n >= target:
            break
        sample.append(s)
        n += len(s.tokens)
    return sample


def compare(params, conf, sample, *, control=False):
    """The widest gap of the sample (and of the control), with the
    count of tokens compared."""
    import reference
    worst, worst_low, n = 0.0, 0.0, 0
    for s in sample:
        g, gl = reference.gaps(params, conf, s.req.prompt, s.tokens,
                               control=control)
        worst = max(worst, float(g.max()))
        if gl is not None:
            worst_low = max(worst_low, float(gl.max()))
        n += len(g)
    out = {"max_gap_logits": worst, "tokens_checked": n}
    if control:
        out["control_max_gap_logits"] = worst_low
    return out


# ------------------------------------------------------------- the run
def build(files, cfg, devices, seed):
    """Weights on the device from the seed; the engine behind the async
    driver at the configuration's sizing, its tracer's rings large
    enough to hold a whole run."""
    import jax

    from repro.api import Session
    from repro.core.sharding import param_shardings
    from repro.core.strategy import Strategy
    from repro.launch.mesh import make_mesh
    from repro.serve.driver import AsyncDriver
    from repro.serve.tracing import Tracer

    import weights

    c = files.config
    data, model = c["mesh"]["data"], c["mesh"]["model"]
    mesh = make_mesh((data, model), ("data", "model"), devices=devices)
    strategy = Strategy(dtype=cfg.dtype)
    shape = flops.Shape.from_config(c)
    shard = param_shardings(weights.shapes(shape, cfg.dtype), strategy,
                            mesh)
    params = jax.block_until_ready(
        weights.make_params(shape, cfg.dtype, seed, shard))
    session = Session(cfg, strategy, mesh, params=params, seed=0)

    def step(driver):
        with jax.profiler.TraceAnnotation("bench.engine_step"):
            driver.engine.step()

    engine = session.serve(tp=model, dp=data, **c["serve"])
    engine.tracer = Tracer(level=engine.tracer.level, max_steps=1 << 15,
                           max_requests=1 << 13)
    driver = AsyncDriver(engine, step_fn=step, start=False)
    return params, session, driver


def device_memory(devices) -> dict:
    out = {}
    for d in devices:
        st = d.memory_stats() or {}
        out[d.id] = {k: st.get(k) for k in
                     ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}
    return out


def run(cell: str, seed: int, seconds: float, trace: bool, *,
        keep_trace: Optional[str] = None, control: bool = False) -> dict:
    """One run of ``cell``; returns the result line's object. With
    ``control``, the reference computed in float8 stands in the
    program's place and its gap decides ``correct``."""
    t_proc = process_start()
    files = cell_files(cell)
    w, mix, load, c = files.workload, files.mix, files.load, files.config
    devices = check_platform(w["chips"])
    import jax
    log(f"compile cache: {enable_compile_cache()}")
    compiles = Compiles()
    cfg = model_config(c)
    shape = flops.Shape.from_config(c)
    pk = peaks_table.peaks(devices[0].device_kind)
    t_import = time.perf_counter()

    params, session, driver = build(files, cfg, devices, seed)
    nbytes = sum(x.nbytes for x in jax.tree.leaves(params))
    log(f"model {c['name']}: {shape.layers} layers, "
        f"{flops.param_count(shape)} parameters, {nbytes} bytes, "
        f"{cfg.dtype}, mesh {c['mesh']}, serve {c['serve']}")
    t_weights = time.perf_counter()
    engine = driver.engine

    # the traffic, and the primer that books the widest bucket first
    vocab = shape.vocab
    max_len = c["serve"]["max_len"]
    if mix["loop"] == "open":
        reqs = traffic.open_loop(mix, load["rate_rps"], seconds, vocab, seed)
        everything = reqs
    else:
        lists = traffic.closed_loop(mix, load["clients"], vocab, seed)
        everything = [r for lst in lists for r in lst]
    primer = traffic.primer(everything, max_len, vocab, seed)
    driver.start()
    t_built = time.perf_counter()
    s0 = Sent(primer, t_built, sent=t_built)
    th = threading.Thread(target=consume, args=(driver, s0), daemon=True)
    th.start()
    th.join(PRIME_TIMEOUT_S)
    if s0.error is not None or not s0.done:
        raise RunFailed(f"the primer did not complete: {s0.error}")
    t_primed = time.perf_counter()

    warm = float(mix["warmup_s"])
    if mix["loop"] == "open":
        origin = t_primed + warm + 0.05
        load_gen = OpenLoop(driver, reqs, origin)
        load_gen.start()
        t_open = origin
    else:
        # the window opens on full slots, and not before the warm-up
        load_gen = ClosedLoop(driver, lists)
        load_gen.start()
        t_open = max(t_primed + warm, load_gen.wait_filled(FILL_TIMEOUT_S))
    traces_before = engine.stats.get("decode_traces")
    time.sleep(max(0.0, t_open - time.perf_counter()))
    setup_s = t_open - t_proc
    t_close = t_open + seconds

    prof = None
    if trace:
        import tempfile
        prof = SimpleNamespace(dir=tempfile.mkdtemp(prefix="bench-prof-"))
        tw = min(TRACE_WINDOW_S, seconds)
        prof.t0 = t_open + (seconds - tw) / 2
        time.sleep(max(0.0, prof.t0 - time.perf_counter()))
        jax.profiler.start_trace(prof.dir)
        prof.ann = jax.profiler.TraceAnnotation("bench.trace_window")
        prof.ann.__enter__()
        prof.t0 = time.perf_counter()
        time.sleep(max(0.0, prof.t0 + tw - time.perf_counter()))
        prof.t1 = time.perf_counter()
        prof.ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
    time.sleep(max(0.0, t_close - time.perf_counter()))
    in_window_compiles = compiles.between(t_open, t_close)
    traces_after = engine.stats.get("decode_traces")

    # close: open loop drains the window's requests, closed loop stops
    if mix["loop"] == "open":
        load_gen.stop()
        drain_limit = t_close + float(mix["drain_s"])
        window_requests = [s for s in load_gen.sent if s.req.in_window]
        load_gen.join(max(0.0, drain_limit - time.perf_counter()))
    else:
        load_gen.stop()
        drain_limit = t_close
    driver.stop(drain=False)
    load_gen.join(30.0)
    sent = list(load_gen.sent)
    if mix["loop"] == "closed":
        # every request in flight at some time in the window, those cut
        # short by the driver's stop among them
        window_requests = [s for s in sent
                           if s.sent < t_close and (s.finished is None
                                                    or s.finished >= t_open)]

    mem = device_memory(devices)
    peak = max((m["peak_bytes_in_use"] or 0) for m in mem.values())
    for d, m in mem.items():
        log(f"device {d}: bytes_in_use {m['bytes_in_use']}, "
            f"peak_bytes_in_use {m['peak_bytes_in_use']}, "
            f"bytes_limit {m['bytes_limit']}")
    late = [s.sent - s.due for s in sent if s.sent]
    if late:
        log(f"load generator lateness: p99 "
            f"{1e3 * traffic.percentile(late, 0.99):.3f} ms, max "
            f"{1e3 * max(late):.3f} ms over {len(late)} requests")
    blocked = [s.submitted - s.sent for s in sent if s.submitted]
    if blocked:
        log(f"client blocked in driver.submit: p50 "
            f"{1e3 * traffic.percentile(blocked, 0.5):.3f} ms, p90 "
            f"{1e3 * traffic.percentile(blocked, 0.9):.3f} ms, max "
            f"{1e3 * max(blocked):.3f} ms")
    if mix["loop"] == "open" and window_requests:
        v = [ttft(s, drain_limit, t_open) for s in window_requests]
        log("ttft ms over " + str(len(v)) + " requests: " + ", ".join(
            f"p{round(100 * q)} {1e3 * traffic.percentile(v, q):.1f}"
            for q in (0.5, 0.75, 0.9, 1.0)))
    log(f"traces compiled inside the window: {in_window_compiles} "
        f"(jax compile events); decode_traces {traces_before} -> "
        f"{traces_after}")
    log(f"setup_s {setup_s:.3f}: process start to jax+config "
        f"{t_import - t_proc:.3f}, weights+engine {t_weights - t_import:.3f}"
        f", driver start {t_built - t_weights:.3f}, primer (bucket "
        f"program) {t_primed - t_built:.3f}, warm-up traffic "
        f"{t_open - t_primed:.3f}")

    flight = engine.tracer.flight()
    steps = flight["steps"]
    req_traces = {r["rid"]: r for r in flight["finished_requests"]
                  + flight["live_requests"]}
    errors = [s.error for s in sent + [s0] if s.error is not None]
    if mix["loop"] == "open":
        failed = sum(s.error is not None or not s.times
                     or s.times[0] > drain_limit for s in window_requests)
    else:
        failed = sum(s.error is not None for s in window_requests)

    the_run = SimpleNamespace(
        cell=cell, chips=w["chips"], shape=shape, peaks=pk, sent=sent,
        primer=s0, window_requests=window_requests, t_open=t_open,
        t_close=t_close, drain_limit=drain_limit, setup_s=setup_s,
        steps=steps, req_traces=req_traces, trace=None)
    summary = None
    if trace:
        import shutil

        import trace_reduce
        path = trace_reduce.find_xplane(prof.dir)
        if keep_trace:
            os.makedirs(keep_trace, exist_ok=True)
            shutil.copy(path, Path(keep_trace) / f"{cell}.{seed}.xplane.pb")
        summary = trace_reduce.reduce(path, devices=len(devices))
        shutil.rmtree(prof.dir, ignore_errors=True)
        the_run.trace = summary

    # free the program's state, then the reference
    del engine, driver, session, flight
    gc.collect()
    log(f"after freeing the engine: "
        f"{ {d: m['bytes_in_use'] for d, m in device_memory(devices).items()} }")
    if mix["loop"] == "open":
        candidates = [s for s in window_requests if s.done and s.tokens]
    else:
        candidates = [s for s in window_requests if s.tokens]
    sample = check_sample(candidates, seed)
    limit = load["check"]["max_gap_logits"]
    t_ref = time.perf_counter()
    chk = compare(params, c, sample, control=control)
    log(f"reference over {len(sample)} requests, {chk['tokens_checked']} "
        f"tokens: {time.perf_counter() - t_ref:.1f}s")
    # the control puts the reference one precision step down in the
    # program's place: its gap, not the program's, then decides
    judged = chk["control_max_gap_logits" if control else "max_gap_logits"]
    correct = (not errors and chk["tokens_checked"] >= CHECK_MIN_TOKENS
               and judged <= limit)

    metrics = {}
    if not trace:
        for m in files.bench["end_to_end"]:
            if applies(m, cell):
                v = end_to_end(m["name"], the_run)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in files.bench["per_layer"]:
            if applies(m, cell):
                v = layer_metric(m["name"], the_run)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": len(window_requests),
           "failed": int(failed), "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        out["breakdown"] = {"device_ops": summary["top_ops"],
                            "idle_gaps": summary["idle_gaps"]}
    check = {}
    if control:
        check["control_max_gap_logits"] = {
            "value": chk["control_max_gap_logits"], "limit": limit}
    check["max_gap_logits"] = {"value": chk["max_gap_logits"],
                               "limit": limit}
    check["tokens_checked"] = {"value": chk["tokens_checked"],
                               "limit": CHECK_MIN_TOKENS}
    if errors:
        check["request_errors"] = {"value": len(errors), "limit": 0}
        log(f"first request error: {errors[0]}")
    out["check"] = check
    log(f"check: max_gap_logits {chk['max_gap_logits']!r} (limit "
        f"{limit!r}), tokens_checked {chk['tokens_checked']} (at least "
        f"{CHECK_MIN_TOKENS})"
        + (f", control_max_gap_logits {chk['control_max_gap_logits']!r}"
           if control else "")
        + (f", request_errors {len(errors)} (limit 0)" if errors else ""))
    return out
