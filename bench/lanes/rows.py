"""The rows lane: vision delivery, the morph plus the Aug-Conv first layer,
at a configuration's ``geometry`` (p=3, stride 1, pad 1).

A lane module gives the harness (``bench/harness.py``):

* ``serve_flags(config, seed)``: the front door's flags
  (``launch/serve.parse_args``);
* ``loadgen_fields(config)``: what the load generator's spec adds to the
  traffic mix for this lane's load side (``<lane>_load.py``);
* ``reachable_shapes(engine, cell)`` and ``warm(engine, shapes)``: every
  shape the cell's traffic can reach, and the warm-up at each;
* ``secrets(front, config)``: what the check reads of the tenants' secrets
  before the front door closes;
* ``checks(config, spec, sample, secrets, seed)``: ``(name, value, limit,
  the value has to be at least the limit)`` for each number the check
  compares, from the window's sample;
* ``record_fields(config)``: fields of the run's record that the lane's
  metric readers use (here ``geom``: the roofline readers count a step's
  work with ``bench/work.py`` at it, through ``bench/readout.per_step``);
* ``note(flush_work)``: the work of each step a traced flush dispatches,
  one tuple a step, which the readers find in ``run.trace["work"]`` behind
  the time it was dispatched.
"""
from __future__ import annotations

import numpy as np

from bench import reference


def serve_flags(config: dict, seed: int) -> list[str]:
    g = config["geometry"]
    if (g["p"], g["stride"], g["pad"]) != (3, 1, 1):
        raise ValueError("the front door serves p=3, stride 1, pad 1 only")
    return [
        "--mode", "serve", "--port", "0", "--seed", str(seed),
        "--channels", str(g["alpha"]), "--out-channels", str(g["beta"]),
        "--image-size", str(g["m"]), "--kappa", str(g["kappa"]),
        "--tenants", str(config["tenants"]),
        "--capacity", str(config["capacity"]),
        *config.get("front_door_flags", []),
    ]


def loadgen_fields(config: dict) -> dict:
    g = config["geometry"]
    return {"channels": g["alpha"], "image_size": g["m"]}


def reachable_shapes(engine, cell) -> list[tuple[int, int]]:
    """Every (G, B) microbatch bucket the cell's traffic can produce.

    G: any number of groups up to the slot capacity, bucketed.  B: requests
    whose image count is a multiple of the engine's chunk arrive as whole
    chunks, so only the chunk size; otherwise any chunk length, bucketed.
    """
    from repro.runtime.queue import bucketize

    q = engine.queue
    groups = min(q.group_buckets[-1], engine.registry.capacity)
    gs = sorted({bucketize(n, q.group_buckets) for n in range(1, groups + 1)})
    per = int(cell.traffic["images_per_request"])
    bs = ([engine.max_rows] if per % engine.max_rows == 0
          else sorted({bucketize(n, q.row_buckets)
                       for n in range(1, engine.max_rows + 1)}))
    return [(g, b) for g in gs for b in bs]


def warm(engine, shapes) -> None:
    """Run the engine's own device step once at every shape."""
    plan = engine._refresh_plan()
    f_in, cap = engine.registry.geom.in_features, engine.registry.capacity
    for g, b in shapes:
        x = np.zeros((g, b, f_in), np.float32)
        gidx = (np.arange(g) % cap).astype(np.int32)
        np.asarray(engine._execute(x, gidx, plan))


def secrets(front, config: dict) -> dict[int, np.ndarray]:
    """Each tenant's secret channel permutation, from its provider's record
    (the front door registers tenant ``i`` as ``tenant-i``)."""
    reg = front.engine.registry
    return {i: np.array(reg.session(f"tenant-{i}").provider._perm)
            for i in range(config["tenants"])}


def checks(config: dict, spec: dict, sample: dict, perms: dict,
           seed: int) -> list[tuple]:
    """The sample held to the float64 reference (``bench/reference.py``)."""
    geom, tenants = config["geometry"], config["tenants"]
    kernels = reference.developer_kernels(geom, tenants, seed)
    cmp = reference.compare(sample, geom, kernels, perms)
    images = tenants * spec["check_per_tenant"] * spec["images_per_request"]
    return [
        ("max_abs_err", cmp["max_abs_err"], config["limits"]["max_abs_err"],
         False),
        ("unpermuted_tenants", reference.unpermuted(perms, geom["beta"]), 0,
         False),
        ("compared_tenants", cmp["compared_tenants"], tenants, True),
        ("compared_images", cmp["compared_images"], images, True),
    ]


def record_fields(config: dict) -> dict:
    return {"geom": dict(config["geometry"])}


def note(flush_work) -> list[tuple]:
    """``(rows, tenants, G, B)`` of each vision microbatch: its real rows,
    the distinct tenants they come from, and its shape."""
    out = []
    for item in flush_work.items:
        if item.lane != "vision":
            continue
        mb = item.mb
        slots = {int(mb.group_tenant[s.group]) for s in mb.slices}
        out.append((sum(s.n_rows for s in mb.slices), len(slots),
                    *mb.x.shape[:2]))
    return out
