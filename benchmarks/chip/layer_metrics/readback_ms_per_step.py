"""Engine step, host side: the mean over the window's steps of the time
a step spends copying the sampled tokens to the host once they are
ready (its ``readback`` span). Nothing when the program records no
child spans. Moves ``itl_p95_ms``."""


def read(run):
    steps = [s for s in run.steps if run.t_open <= s["t0"] < run.t_close]
    if not any("spans" in s for s in steps):
        return None
    ms = [sum(sp["end"] - sp["start"] for sp in s.get("spans", ())
              if sp["name"] == "readback") for s in steps]
    return 1e3 * sum(ms) / len(ms)
