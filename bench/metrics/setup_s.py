"""Set-up: process start to the window's first timed request, compiles,
weights and warm-up included (host clock)."""


def read(run):
    return run["setup_s"]
