"""Device: 1 - (the union of the intervals in which an operation ran
on the chip) / (the traced window), from the profiler trace, on the
chip where it is highest, in %. Moves ``itl_p95_ms``."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * max(d["idle_share"] for d in run.trace["devices"].values())
