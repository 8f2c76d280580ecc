"""Median wait from a request's submission to the moment the engine first
admitted it (``ServeRequest.admitted_ts - submit_ts``, both the program's
own clock), over the requests due in the window and not failed; one still
waiting at the window's end enters at its wait so far.  Unlike
``queue_wait_p50_ms`` it does not run to the end of the admitting step."""
from bench.measure import percentile


def read(run):
    reqs = [f.req for f in run.due_in_window()
            if not f.failed and f.req is not None and not f.req.failed]
    if not reqs or not all(hasattr(r, "admitted_ts") for r in reqs):
        return None  # none due, or a program that does not stamp admission
    vals = [
        (run.t1 if r.admitted_ts is None else min(r.admitted_ts, run.t1)) - r.submit_ts
        for r in reqs
    ]
    run.notes["admit_samples"] = len(vals)
    return 1e3 * percentile(vals, 50)
