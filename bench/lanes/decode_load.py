"""The decode lane, load side: one generation a request, sent as a
``"lane": "decode"`` frame (a prompt's token ids and ``max_new_tokens``).
Imports no JAX.

``bench/lanes/rows_load.py`` says what a load module gives.  Here request
``offset`` is a prompt drawn from the seed and the offset: its length is
lognormal (the traffic's ``prompt_tokens``: median, sigma, and the clip to
``[min, max]``), its ids uniform over the spec's ``vocab``.  Each response
adds the ``tokens`` column (ids generated); the sample keeps each kept
request's prompt, the ids served and their logits (the response header's
``scores``), concatenated, with the lengths to split them.

The number of tokens to generate follows ``output_tokens`` (the same
lognormal law) drawn in blocks of ``STRATA`` requests, in the order they
are sent: a block takes one draw from each of the ``STRATA`` equal-chance
ranges of the distribution, in an order and at points drawn from the seed.
Each request's length keeps the lognormal law, and neighbours are nearly
independent (long answers can come in a row), but every block covers the
distribution.  A generation's latency is about proportional to its length,
so with independent draws the window's median latency would follow the
median of about 240 lognormal draws: six seeds' quartiles would spread
about 9%, past half the metric's bound; in blocks of 16, about 1.5%.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

STRATA = 16


def _clip(n: float, spec: dict) -> int:
    return int(np.clip(round(n), spec["min"], spec["max"]))


def lognormal_len(rng, spec: dict) -> int:
    return _clip(rng.lognormal(np.log(spec["median"]), spec["sigma"]), spec)


def lognormal_quantile(u: float, spec: dict) -> int:
    """The clipped lognormal's length at quantile ``u`` in (0, 1)."""
    z = NormalDist().inv_cdf(min(max(u, 1e-12), 1.0 - 1e-12))
    return _clip(spec["median"] * math.exp(spec["sigma"] * z), spec)


def prompt(spec: dict, seed: int, offset: int) -> np.ndarray:
    """Prompt ids of request ``offset``."""
    rng = np.random.default_rng([seed, 5, offset])
    n = lognormal_len(rng, spec["prompt_tokens"])
    return rng.integers(0, spec["vocab"], n).astype(np.int32)


def answer_len(spec: dict, seed: int, i: int) -> int:
    """``max_new_tokens`` of the ``i``-th request sent: block ``i //
    STRATA`` takes one quantile from each of its ``STRATA`` ranges."""
    rng = np.random.default_rng([seed, 6, i // STRATA])
    rank = rng.permutation(STRATA)[i % STRATA]
    u = (rank + rng.random(STRATA)[i % STRATA]) / STRATA
    return lognormal_quantile(u, spec["output_tokens"])


class Load:
    columns = ("tokens",)
    images = 1

    def __init__(self, spec: dict, seed: int):
        self.spec, self.seed = spec, seed
        self.choices = int(spec["pool_prompts"])
        self.sent = 0

    def request(self, offset: int) -> tuple[dict, bytes]:
        ids = prompt(self.spec, self.seed, offset)
        out = answer_len(self.spec, self.seed, self.sent)
        self.sent += 1
        return {
            "lane": "decode", "deliver": "tokens", "priority": 0,
            "deadline_ms": None, "age_ms": 0.0, "metadata": {},
            "dtype": "int32", "shape": [int(ids.size)],
            "max_new_tokens": out,
        }, ids.tobytes()

    def response(self, header: dict, payload: bytes) -> tuple:
        return (int(np.prod(header["shape"])),)

    def keep(self, header: dict, payload: bytes) -> tuple:
        ids = np.frombuffer(payload, np.dtype(header["dtype"])).astype(np.int32)
        return ids, np.asarray(header.get("scores", []), np.float32)

    def sample(self, kept: list) -> dict:
        prompts = [prompt(self.spec, self.seed, o) for o, _ in kept]
        cat = (lambda xs, dt: np.concatenate(xs).astype(dt) if xs
               else np.zeros((0,), dt))
        return {
            "prompts": cat(prompts, np.int32),
            "prompt_len": np.array([p.size for p in prompts], np.int64),
            "served": cat([k[0] for _, k in kept], np.int32),
            "scores": cat([k[1] for _, k in kept], np.float32),
            "served_len": np.array([k[0].size for _, k in kept], np.int64),
        }
