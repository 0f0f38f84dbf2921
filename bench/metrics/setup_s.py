"""Process start to the first timed request: imports, weights, warm-up
and compile-cache loads."""


def read(run):
    return run["setup_s"]
