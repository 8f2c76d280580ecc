"""Output tokens per second: every token of every request that came out
inside the window, over the window's seconds (the last step's end included)."""


def read(run):
    n = sum(1 for f in run.flights for t in f.token_t if run.t0 < t <= run.t1)
    run.notes["window_tokens"] = n
    return n / run.window_s if n else None
