"""The decode lane: LM tenants at the front door, each generation a request
(``lane="decode"``: prompt ids and ``max_new_tokens``), each prompt morphed
and decoded by the engine's batched decode lane.

``bench/lanes/rows.py`` lists what a lane module gives.  Here:

* the configuration names the program's model (``arch``), the depth served
  (``num_hidden_layers``), the tenants, slots, decode rows, cache length and
  prompt buckets;
* every shape the traffic reaches is warmed: each prompt bucket's prefill
  (the decode step itself is warmed by the front door's own warm requests);
* the secrets read before the front door closes are the trunk's weights,
  the developer's embedding and head, and each tenant's permutation;
* the check runs ``bench/decode_reference.py`` on the chip over the sample:
  at each generated position the served logit against the reference's
  logit of the same id, and that id's distance below the reference's
  largest logit, both in units of the reference logits' standard deviation;
* ``note`` hands the readers each decode step's record, which the step
  fills as it runs (rows decoded, their positions and slots, the prefills,
  the experts each MoE layer hit).
"""
from __future__ import annotations

import numpy as np

from bench import decode_reference


def serve_flags(config: dict, seed: int) -> list[str]:
    return [
        "--mode", "serve", "--port", "0", "--seed", str(seed),
        "--arch", config["arch"], *(["--smoke"] if config.get("smoke") else []),
        "--layers", str(config["num_hidden_layers"]),
        "--tenants", str(config["tenants"]),
        "--capacity", str(config["capacity"]),
        "--rows", str(config["rows"]),
        "--max-len", str(config["max_len"]),
        "--prompt-buckets", ",".join(str(b) for b in config["prompt_buckets"]),
        *config.get("front_door_flags", []),
    ]


def loadgen_fields(config: dict) -> dict:
    return {"vocab": config["vocab_size"]}


def reachable_shapes(engine, cell) -> list[tuple]:
    """Each prompt bucket's prefill."""
    return [("prefill", b) for b in engine.decode_lane.prompt_buckets]


def warm(engine, shapes) -> None:
    import jax.numpy as jnp

    lane = engine.decode_lane
    plan = lane._refresh_plan()
    for _, n in shapes:
        out = lane._prefill(
            lane.params, plan.arrays["aug_embeds"], plan.arrays["aug_heads"],
            jnp.asarray(0, jnp.int32), jnp.zeros((1, n), jnp.int32),
            jnp.asarray(n, jnp.int32))
        np.asarray(out[0])


def secrets(front, config: dict) -> dict:
    """Host copies of the trunk's weights, the developer's embedding and
    head (one model: every tenant registered the same arrays), and each
    tenant's vocab permutation."""
    import jax

    eng = front.engine
    reg = eng.lm_registry
    s0 = reg.session("tenant-0")
    return {
        "params": jax.device_get(eng.decode_lane.params),
        "embed": s0.embedding,
        "head": s0.head,
        "perms": {i: np.array(reg.session(f"tenant-{i}").morpher.perm)
                  for i in range(config["tenants"])},
    }


def _split(flat: np.ndarray, lens: np.ndarray) -> list:
    return np.split(flat, np.cumsum(lens)[:-1]) if len(lens) else []


def checks(config: dict, spec: dict, sample: dict, secrets: dict,
           seed: int) -> list[tuple]:
    """The sample held to the float32 reference on the chip."""
    prompts = _split(sample["prompts"], sample["prompt_len"])
    served = _split(sample["served"], sample["served_len"])
    scores = _split(sample["scores"], sample["served_len"])
    tenants = config["tenants"]
    err = gap = 0.0
    compared = 0
    if prompts:
        ref = decode_reference.logit_stats(
            secrets, config, list(zip(prompts, served)))
        got = np.concatenate(scores).astype(np.float64)
        std = ref["std"].astype(np.float64)
        err = float(np.max(np.abs(got - ref["picked"]) / std))
        gap = float(np.max((ref["max"] - ref["picked"]) / std))
        compared = int(got.size)
    tau = config["limits"]["logit_err_std"]
    identity = sum(bool(np.array_equal(p, np.arange(p.size)))
                   for p in secrets["perms"].values())
    return [
        ("max_logit_err", err, tau, False),
        ("max_choice_gap", gap, tau, False),
        ("unpermuted_tenants", identity, 0, False),
        ("compared_tenants", len(set(sample["tenant"].tolist())), tenants, True),
        ("compared_sequences", len(prompts),
         tenants * spec["check_per_tenant"], True),
        ("compared_tokens", compared, int(sample["served_len"].sum()), True),
    ]


def record_fields(config: dict) -> dict:
    return {"lm": dict(config)}


def note(flush_work) -> list[tuple]:
    """``("decode", record)`` of each decode step: the record the step fills
    as it runs (``runtime.decode.DecodeWork.record``)."""
    return [("decode", item.mb.record) for item in flush_work.items
            if item.lane == "decode"]
