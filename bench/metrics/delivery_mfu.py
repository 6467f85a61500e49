"""The whole delivery step's share of the chip's bf16 peak: the FLOP its real
rows need over the device time of the jitted ``_delivery_step`` programs in
the traced window, as means per step."""
from bench.readout import per_step, step_seconds


def read(run):
    got = per_step(run, step_seconds(run))
    if got is None:
        return None
    return 100.0 * got[1] / (got[2] * run.trace["peak"]["bf16_flop_per_s"])
