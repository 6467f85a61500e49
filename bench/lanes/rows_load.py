"""The rows lane, load side: single images or minibatches of images, sent as
``"lane": "rows"`` frames.  Imports no JAX.

``bench/loadgen.py`` loads ``bench/lanes/<lane>_load.py`` by the spec's
``lane`` and makes one ``Load(spec, seed)``.  A load module gives:

* ``choices``: how many payloads a request can draw from; the load generator
  draws each request's ``offset`` in ``[0, choices)`` from the seed;
* ``images``: the ``images`` column of ``requests.json`` for each request;
* ``columns``: further columns of ``requests.json`` (here none), filled for
  each response by ``response(header, payload)``;
* ``request(offset)``: the request frame's header fields after ``rid`` and
  ``tenant``, and its body;
* ``keep(header, payload)``: what the sample keeps of a response;
* ``sample(kept)``: the arrays of ``sample.npz`` for the kept ``(offset,
  what keep returned)`` pairs, beside the load generator's ``idx`` and
  ``tenant``.

Here the spec's ``channels`` and ``image_size`` set the images, a pool of
``pool_images`` drawn from the seed, and a request sends
``images_per_request`` consecutive images of the pool.
"""
from __future__ import annotations

import numpy as np


def image_pool(spec: dict, seed: int) -> np.ndarray:
    rng = np.random.default_rng([seed, 0])
    c, m = spec["channels"], spec["image_size"]
    return rng.standard_normal((spec["pool_images"], c, m, m), np.float32)


def request_fields(images: np.ndarray) -> tuple[dict, bytes]:
    """The header fields and body of a request for ``images``."""
    images = np.ascontiguousarray(images, np.float32)
    return {
        "lane": "rows", "deliver": "tokens", "priority": 0,
        "deadline_ms": None, "age_ms": 0.0, "metadata": {},
        "dtype": "float32", "shape": list(images.shape),
    }, images.tobytes()


def decode_array(header: dict, payload: bytes) -> np.ndarray:
    return np.frombuffer(payload, np.dtype(header["dtype"])).reshape(
        header["shape"]
    ).copy()


class Load:
    columns = ()

    def __init__(self, spec: dict, seed: int):
        self.images = int(spec["images_per_request"])
        self.pool = image_pool(spec, seed)
        self.choices = spec["pool_images"] - self.images + 1

    def _images(self, offset: int) -> np.ndarray:
        return self.pool[offset : offset + self.images]

    def request(self, offset: int) -> tuple[dict, bytes]:
        return request_fields(self._images(offset))

    def response(self, header: dict, payload: bytes) -> tuple:
        return ()

    def keep(self, header: dict, payload: bytes) -> np.ndarray:
        return decode_array(header, payload)

    def sample(self, kept: list) -> dict:
        return {
            "images": np.stack([self._images(o) for o, _ in kept])
            if kept else np.zeros((0,), np.float32),
            "served": np.stack([p for _, p in kept])
            if kept else np.zeros((0,), np.float32),
        }
