"""The routed experts' kernel against its roofline: the least time the MoE
layers' expert products need (bench/decode_work.py: the weights of each
expert hit once and the (token, expert) pairs' activations, or the FLOP at
the bf16 peak), summed over the decode steps and prefills dispatched in the
traced window, over the device time of the operations named
``mole_expert_gmm`` in it."""
from bench import decode_work
from bench import trace as tr


def read(run):
    t = run.trace
    if t is None or t["peak"] is None:
        return None
    lo, hi = t["win"]
    seconds = sum(
        d for plane in tr.device_planes(t["plain"])
        for name, s, d in tr._ops(plane)
        if tr.op_name(name).startswith("mole_expert_gmm") and lo <= s < hi
    ) / 1e9
    c = run.lm
    least = sum(
        decode_work.least_seconds(*decode_work.expert_layer(c, pairs, hit),
                                  t["peak"])
        for step in t["work"] if step[1] == "decode" and "hits" in step[2]
        for pairs, hit in decode_work.expert_calls(c, step[2])
    )
    if not seconds or not least:
        return None
    return 100.0 * least / seconds
