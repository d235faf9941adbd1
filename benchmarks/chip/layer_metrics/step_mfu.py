"""Model step: useful model FLOPs of the window's steps over (their
summed wall time x chips x the chip's bf16 peak), in %. Useful FLOPs
are flops.py's: decode and prefill-chunk rows at their own context
length, the LM head only where a token is sampled, pad rows nothing.
Row positions are replayed from the tracer's per-step work items and
each request's prompt length. Moves ``itl_p95_ms``."""
import flops


def rows_by_step(run):
    """``{step_id: [(position, sampled), ...]}`` for every recorded
    step, replayed in order from the start of the run."""
    prompt = {s.rid: len(s.req.prompt) for s in run.sent + [run.primer]
              if s.rid is not None}
    pos, produced, out = {}, {}, {}
    for st in sorted(run.steps, key=lambda s: s["step_id"]):
        rows = []
        for w in st["work"]:
            rid = w["rid"]
            if w["kind"] == "prefill":
                a, n = w["start"], w["count"]
                ctx = prompt[rid] + produced.get(rid, 0)
                rows += [(p, p == ctx - 1) for p in range(a, a + n)]
                pos[rid] = a + n
                if a + n == ctx:
                    produced[rid] = produced.get(rid, 0) + 1
            else:
                p = pos.get(rid, prompt.get(rid, 0))
                rows += [(p + i, True) for i in range(w["tokens"])]
                pos[rid] = p + w["tokens"]
                produced[rid] = produced.get(rid, 0) + w["tokens"]
        out[st["step_id"]] = rows
    return out


def read(run):
    steps = [s for s in run.steps if run.t_open <= s["t0"] < run.t_close]
    wall = sum(s["dur"] for s in steps)
    if not steps or wall <= 0:
        return None
    rows = rows_by_step(run)
    useful = sum(flops.step_flops(run.shape, rows[s["step_id"]])
                 for s in steps)
    return 100.0 * useful / (wall * run.chips * run.peaks["bf16_flops"])
