"""Process start to window start: imports, chip start-up, weights and
payloads, warm-up (compiles, or loads from the cache), the first
training steps."""


def read(run):
    return run.setup_s
