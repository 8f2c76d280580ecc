"""Median time to first token, from the moment each request was due, over
every request due in the window.  A request with no first token by the
window's end enters at the time it has waited; a failed one as infinite.

The median, and no higher percentile, because it is the highest one for which
the window's requests (20 at this cell's rate and 50 s) leave ten or more
samples beyond it; the 75th to 90th are printed beside it (info).  A per-layer
metric of open-loop cells: over 20 requests its runs spread too widely for an
end-to-end bound (PERF.md section 2)."""
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
