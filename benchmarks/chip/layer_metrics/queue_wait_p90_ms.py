"""Driver and scheduler: the 90th percentile, over the requests due in
the window, of the wait from a request's due time to the engine
tracer's ``admitted`` event (both on ``perf_counter``). A request never
admitted reads the drain limit less the window's open and a second
more, above any wait of a request due in the window. Moves
``ttft_p90_ms``."""
import traffic


def read(run):
    if not run.window_requests:
        return None
    waits = []
    for s in run.window_requests:
        tr = run.req_traces.get(s.rid) or {}
        t = next((e["t"] for e in tr.get("events", ())
                  if e["kind"] == "admitted"), None)
        waits.append(t - s.due if t is not None
                     else run.drain_limit - run.t_open + 1.0)
    return 1e3 * traffic.percentile(waits, 0.90)
