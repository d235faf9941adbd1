"""Driver and scheduler: the 90th percentile, over the requests due in
the window, of the time ``driver.submit`` waited for the driver's lock
(``lock_wait_s`` on the engine tracer's ``submitted`` event). Nothing
when the program records no such wait. Moves ``itl_p95_ms``."""
import traffic


def read(run):
    waits = []
    for s in run.window_requests:
        tr = run.req_traces.get(s.rid) or {}
        w = next((e.get("lock_wait_s") for e in tr.get("events", ())
                  if e["kind"] == "submitted"), None)
        if w is not None:
            waits.append(w)
    if not waits:
        return None
    return 1e3 * traffic.percentile(waits, 0.90)
