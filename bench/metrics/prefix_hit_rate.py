"""Share of admitted prompt tokens served from cached pages in the window
(EngineStats.prefix_hit_tokens over hit + new tokens, as changes)."""


def read(run):
    hit = run.delta("prefix_hit_tokens")
    total = hit + run.delta("prefix_new_tokens")
    return 100.0 * hit / total if total else None
