"""Real rows per microbatch the scheduler coalesced in the window."""


def read(run):
    s = run.stats
    return s["rows_in"] / s["microbatches"] if s["microbatches"] else None
