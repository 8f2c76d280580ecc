"""Mean time of one decode or speculative group call, from dispatch to its
result being ready on the host: the ``serve.call`` spans of the
``serve_decode`` and ``serve_spec`` programs (EngineStats ``decode_wait_s``
over ``decode_steps``, as changes over the window)."""


def read(run):
    if "decode_wait_s" not in run.stats1:
        return None  # a program without the counter
    calls = run.delta("decode_steps")
    run.notes["decode_calls"] = calls
    return 1e3 * run.delta("decode_wait_s") / calls if calls else None
