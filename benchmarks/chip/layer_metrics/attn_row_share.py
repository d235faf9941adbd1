"""Kernels: the share of the token budget's rows that the mixed step's
paged attention ran over, in %: the sum over the window's steps of the
attention's row width (``attn_rows`` on the engine tracer's step record)
over the sum of their token budgets (``budget``). Steps that launched no
program carry neither and are left out. Nothing when the program records
no row width. Moves ``itl_p95_ms``."""


def read(run):
    steps = [s for s in run.steps if run.t_open <= s["t0"] < run.t_close
             and s.get("attn_rows") is not None]
    budget = sum(s["budget"] for s in steps)
    if not budget:
        return None
    return 100.0 * sum(s["attn_rows"] for s in steps) / budget
