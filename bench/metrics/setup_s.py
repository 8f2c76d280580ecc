"""Process start to the first timed step: imports, weights, engine, warm-up
of every step program, and the traffic's roll-forward."""


def read(run):
    return run.setup_s
