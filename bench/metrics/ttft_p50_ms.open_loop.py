"""Median time to first token, from the moment each request was due, over
every request due in the window.  A request with no first token by the
window's end enters at the time it has waited; a failed one as infinite.

The median; the 75th to 90th are printed beside it (info).  A per-layer
metric: where arrivals outrun the engine, every request waits in a queue that
grows through the window, so the wait swings with the smallest change in speed
(PERF.md section 2)."""
import math

from bench.measure import percentile, samples_beyond


def read(run):
    if run.closed:
        return None
    vals = []
    for f in run.due_in_window():
        if f.failed or (f.req is not None and f.req.failed):
            vals.append(math.inf)
        elif f.token_t and f.token_t[0] <= run.t1:
            vals.append(f.token_t[0] - f.due_t)
        else:
            vals.append(run.t1 - f.due_t)
    if not vals:
        return None
    run.notes["ttft_requests"] = len(vals)
    run.notes["ttft_beyond_p50"] = samples_beyond(len(vals), 50)
    for p in (75, 80, 90):
        run.notes[f"ttft_p{p}_ms"] = 1e3 * percentile(vals, p)
    return 1e3 * percentile(vals, 50)
