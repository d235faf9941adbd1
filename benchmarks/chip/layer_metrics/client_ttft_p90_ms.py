"""Client: the 90th percentile, over the requests due in the window, of
the time from a request's due time to its first token at the client;
a request that failed or had no first token by the drain limit reads
above any latency a request due in the window can have. Timed at the
client as the end-to-end metrics are, but too unsteady to gate a
change: the wait to get into ``driver.submit`` past the step loop's
lock sets most of it, and that wait differs from run to run on one
seed. Moves ``itl_p95_ms``: every step of a request's prefill and
its first token is one gap of that size."""
import traffic

from harness import ttft


def read(run):
    if not run.window_requests:
        return None
    v = [ttft(s, run.drain_limit, run.t_open) for s in run.window_requests]
    return 1e3 * traffic.percentile(v, 0.90)
