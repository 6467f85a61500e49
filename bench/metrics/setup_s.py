"""Process start to window open: imports, registration, uploads, warm-up,
compiles or cache loads, and the load generator's start."""


def read(run):
    return run.setup_s
