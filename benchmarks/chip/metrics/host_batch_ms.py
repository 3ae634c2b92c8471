"""Host time per step spent fetching the next batch from the program's
Batcher, timed by the benchmark around each ``next()`` in the window."""


def read(run):
    b = run["record"]["batch_s"]
    return 1e3 * sum(b) / len(b) if b else None
