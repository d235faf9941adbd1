"""Engine step, host side: the mean over the window's steps of the time
a step spends putting its inputs on the device (its ``inputs`` spans:
the page-table push and the step program's arguments). Nothing when
the program records no child spans. Moves ``itl_p95_ms``."""


def read(run):
    steps = [s for s in run.steps if run.t_open <= s["t0"] < run.t_close]
    if not any("spans" in s for s in steps):
        return None
    ms = [sum(sp["end"] - sp["start"] for sp in s.get("spans", ())
              if sp["name"] == "inputs") for s in steps]
    return 1e3 * sum(ms) / len(ms)
