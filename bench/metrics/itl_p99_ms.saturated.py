"""99th percentile of the gaps between consecutive output tokens of a
request, over every gap that closes inside the window, all requests.

Per layer, not end to end: over a 50 s window its runs spread 15-23 % (the
p99 falls on one or another step composition, a chunk call more or fewer),
wider than any bound the check admits (PERF.md section 2)."""
from bench.measure import percentile


def read(run):
    gaps = []
    for f in run.flights:
        ts = f.token_t
        gaps += [b - a for a, b in zip(ts, ts[1:]) if run.t0 < b <= run.t1]
    if not gaps:
        return None
    run.notes["itl_gaps"] = len(gaps)
    return 1e3 * percentile(gaps, 99)
