"""Median wait from a request's due time to the end of the first engine step
after which it had left the waiting queue, over the requests due in the
window (those still waiting enter at their wait so far).  The median for the
reason ``ttft_p50_ms.open_loop`` gives."""
from bench.measure import percentile


def read(run):
    if run.closed:
        return None
    vals = [
        (min(f.admitted_t, run.t1) if f.admitted_t is not None else run.t1) - f.due_t
        for f in run.due_in_window()
        if not f.failed
    ]
    return 1e3 * percentile(vals, 50) if vals else None
