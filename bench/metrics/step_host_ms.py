"""Host time per engine step outside the chip calls: the seconds inside
``ServeEngine.step()`` less those inside its ``serve.call`` spans of decode,
spec and chunk programs (EngineStats ``step_s - decode_wait_s -
chunk_wait_s``, as changes over the window), per engine step.  The device
idle that the engine itself causes, since every chip call blocks."""

COUNTERS = ("step_s", "decode_wait_s", "chunk_wait_s", "engine_steps")


def read(run):
    if any(c not in run.stats1 for c in COUNTERS):
        return None  # a program without these counters
    steps = run.delta("engine_steps")
    run.notes["window_steps_counted"] = steps
    if not steps:
        return None
    host = run.delta("step_s") - run.delta("decode_wait_s") - run.delta("chunk_wait_s")
    return 1e3 * host / steps
