"""Share of the rows the scheduler padded in (runtime/queue.py), of all rows
it sent to the device in the window."""


def read(run):
    s = run.stats
    total = s["rows_in"] + s["rows_padded"]
    return 100.0 * s["rows_padded"] / total if total else None
