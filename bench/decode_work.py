"""The work of the decode lane's device programs, counted from the
configuration's numbers (``bench/configs/<config>.json``, the catalog's keys).

* A decode step has to read every trunk weight once, the Aug-head of each
  slot its live rows use, and each live row's latent cache up to its
  position; its FLOP are two a weight a live row (MoE layers: the shared
  and ``num_experts_per_tok`` routed experts a row uses), plus the head.
* One MoE layer's routed experts (the kernel ``mole_expert_gmm``: gate, up
  and down products over the live tokens' (token, expert) pairs grouped by
  expert) have to read the weights of each expert hit once, and the pairs'
  activations in and out; their FLOP are ``2 * pairs * 3 * d * f``.

Everything in bfloat16, the configuration's precision.  Least time is the
larger of bytes over HBM bandwidth and FLOP over the bf16 peak.
"""
from __future__ import annotations

BF16 = 2


def _dims(c: dict) -> dict:
    return {
        "d": c["hidden_size"], "V": c["vocab_size"],
        "H": c["num_attention_heads"], "lora": c["kv_lora_rank"],
        "nope": c["qk_nope_head_dim"], "rope": c["qk_rope_head_dim"],
        "v": c["v_head_dim"], "dense": c["intermediate_size"],
        "f": c["moe_intermediate_size"], "E": c["n_routed_experts"],
        "k": c["num_experts_per_tok"], "shared": c["n_shared_experts"],
        "layers": c["num_hidden_layers"],
        "dense_layers": c["first_k_dense_replace"],
    }


def trunk_params(c: dict) -> tuple[int, int]:
    """``(all trunk weights, weights one token uses)``: the layers and the
    final norm, without embedding and head."""
    n = _dims(c)
    d, H = n["d"], n["H"]
    mla = (d * H * (n["nope"] + n["rope"]) + d * n["lora"] + d * n["rope"]
           + n["lora"] + n["lora"] * H * (n["nope"] + n["v"]) + H * n["v"] * d)
    dense = mla + 2 * d + 3 * d * n["dense"]
    expert = 3 * d * n["f"]
    moe_fixed = mla + 2 * d + d * n["E"] + n["shared"] * expert
    moe_layers = n["layers"] - n["dense_layers"]
    total = n["dense_layers"] * dense + moe_layers * (moe_fixed + n["E"] * expert) + d
    used = n["dense_layers"] * dense + moe_layers * (moe_fixed + n["k"] * expert) + d
    return total, used


def decode_step(c: dict, record: dict) -> tuple[float, float]:
    """``(FLOP, bytes)`` one decode step needs, from its record."""
    n = _dims(c)
    total, used = trunk_params(c)
    rows = record["rows"]
    per_pos = n["layers"] * (n["lora"] + n["rope"]) * BF16
    positions = sum(t + 1 for t in record["positions"])
    nbytes = (BF16 * total + BF16 * len(record["slots"]) * n["d"] * n["V"]
              + per_pos * positions)
    attn = 2 * n["layers"] * n["H"] * (n["lora"] * 2 + n["rope"]) * positions
    flop = 2.0 * rows * (used + n["d"] * n["V"]) + attn
    return flop, nbytes


def expert_layer(c: dict, pairs: int, hit: int) -> tuple[float, float]:
    """``(FLOP, bytes)`` of one MoE layer's routed experts over ``pairs``
    (token, expert) pairs that hit ``hit`` distinct experts."""
    n = _dims(c)
    d, f = n["d"], n["f"]
    nbytes = BF16 * (hit * 3 * d * f + pairs * 3 * (d + f))
    return 2.0 * pairs * 3 * d * f, nbytes


def least_seconds(flop: float, nbytes: float, peak: dict) -> float:
    return max(nbytes / peak["hbm_bytes_per_s"], flop / peak["bf16_flop_per_s"])


def expert_calls(c: dict, record: dict) -> list[tuple[int, int]]:
    """``(pairs, experts hit)`` of each MoE layer call a step's record
    covers: its prefills' (each prompt's own tokens: its bucket's padding
    routes to no expert) and the step's (its live rows: idle rows route to
    no expert)."""
    k = c["num_experts_per_tok"]
    calls = [(n * k, h) for n, _, hits in record["prefills"] for h in hits]
    return calls + [(record["rows"] * k, h) for h in record["hits"]]
