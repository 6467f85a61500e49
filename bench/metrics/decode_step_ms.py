"""Median host time of one batched decode step: the program's
``mole.decode.step`` spans (runtime/decode.py ``run_step``: the step's call
and the fetch of its tokens, logits and routing counts) in the traced
window."""
from bench.spans import median_ms


def read(run):
    return median_ms(run, "mole.decode.step")
