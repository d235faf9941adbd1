"""Engine step, host side: the mean over the window's steps of a step's
wall time less its ``dispatch`` and ``sync`` laps (the engine tracer's
``StepTrace``), i.e. the time the host spends scheduling, packing and
doing bookkeeping around the device program. Moves ``itl_p95_ms``."""


def read(run):
    steps = [s for s in run.steps if run.t_open <= s["t0"] < run.t_close]
    if not steps:
        return None
    host = [s["dur"] - s["phases"].get("dispatch", 0.0)
            - s["phases"].get("sync", 0.0) for s in steps]
    return 1e3 * sum(host) / len(host)
