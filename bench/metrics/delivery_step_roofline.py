"""The delivery step's share of its roofline: the least time its work needs
(bench/work.py: the distinct tenants' secrets and the real rows once, or its
FLOP at the bf16 peak) over the device time of the jitted ``_delivery_step``
programs in the traced window, as means per step."""
from bench.readout import per_step, step_seconds


def read(run):
    got = per_step(run, step_seconds(run))
    return None if got is None else 100.0 * got[0] / got[2]
