"""Mean time of one chunked-prefill call, from dispatch to its first tokens
being ready on the host: the ``serve.call`` spans of the ``serve_chunk``
program (EngineStats ``chunk_wait_s`` over ``prefill_chunks``, as changes
over the window)."""


def read(run):
    if "chunk_wait_s" not in run.stats1:
        return None  # a program without the counter
    calls = run.delta("prefill_chunks")
    run.notes["chunk_calls"] = calls
    return 1e3 * run.delta("chunk_wait_s") / calls if calls else None
