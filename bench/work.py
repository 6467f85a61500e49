"""The work a delivery step needs, counted from its shapes.

A step serves one microbatch: ``rows`` real rows from ``tenants`` distinct
tenants, through two kernels: the morph reads each tenant's ``(q, q)`` core
and needs ``2 * rows * F_in * q`` FLOP; the Aug-Conv product reads each
tenant's ``(F_in, F_out)`` matrix and needs ``2 * rows * F_in * F_out`` FLOP.
The step has to read the secrets and the real rows in and out once, all in
float32, the configuration's precision.  Padding rows and padding groups are
not work, so a step that skips them can only come nearer to the bound, never
past it.
"""
from __future__ import annotations

import json
from pathlib import Path

F32 = 4


def shapes(geom: dict) -> dict:
    """``F_in``, ``F_out`` and the core width ``q`` of a geometry."""
    n = (geom["m"] + 2 * geom["pad"] - geom["p"]) // geom["stride"] + 1
    f_in = geom["alpha"] * geom["m"] ** 2
    return {"f_in": f_in, "f_out": geom["beta"] * n * n,
            "q": f_in // geom["kappa"]}


def counts(geom: dict, rows: int, tenants: int) -> tuple:
    """``(FLOP, bytes)`` a delivery step needs."""
    s = shapes(geom)
    f_in, f_out, q = s["f_in"], s["f_out"], s["q"]
    flop = 2.0 * rows * f_in * (q + f_out)
    nbytes = F32 * (tenants * (q * q + f_in * f_out) + rows * (f_in + f_out))
    return flop, nbytes


def least_seconds(geom: dict, rows: int, tenants: int, peak: dict) -> float:
    """The least time the chip could take: bytes over HBM bandwidth or FLOP
    over the bf16 peak, whichever is larger."""
    flop, nbytes = counts(geom, rows, tenants)
    return max(nbytes / peak["hbm_bytes_per_s"], flop / peak["bf16_flop_per_s"])


def peak_of(device_kind: str, root: Path) -> dict:
    """The peaks of ``device_kind`` from ``bench/peaks.json``; a kind that is
    not in the table is an error."""
    table = json.loads((root / "bench" / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (have {sorted(table)})")
    return table[device_kind]
