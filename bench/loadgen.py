"""Load generator for the delivery benchmark.  Imports no JAX.

    python bench/loadgen.py --port PORT --spec SPEC.json --out DIR

It runs in a process of its own beside the server it drives, speaks the
front door's wire frames (the frame layout of ``repro.runtime.wire``,
re-stated here so that this process stays free of JAX), and plays one
traffic mix from ``--seed``:

* ``"loop": "open"`` -- independent clients: request ``i`` is due at a time
  drawn in advance (Poisson arrivals at ``rate_per_s``) and is sent then,
  whether or not earlier requests have completed.  Its latency runs from
  when it was *due* to when its whole response arrived, so a late generator
  cannot hide queueing; how late the generator sent is reported apart.
* ``"loop": "closed"`` -- training jobs: ``jobs`` clients, each with its own
  connection, keep ``depth`` requests in flight and send the next one the
  moment a response lands.

Tenants are drawn from a Zipf law (``popularity.theta``) in the open loop;
in the closed loop job ``j`` is tenant ``j``.  Images come from a pool drawn
from the seed, so the same seed sends the same bytes.

Protocol with the parent, one line each on stdout: ``ready`` once every
connection is open; the window opens when ``go`` arrives on stdin; ``closed``
when it closes (no request is issued after that); ``done`` once every
request issued has an outcome (or the grace period ran out) and the results
are written to ``--out``: ``requests.json`` (one row per request: due, sent
and done seconds from the window's opening, outcome, images, tenant) and
``sample.npz`` (for each tenant, a seeded uniform sample of its requests
served ``ok``: their tenant, the images sent and the features served).
"""
from __future__ import annotations

import argparse
import asyncio
import itertools
import json
import struct
import sys
import time
from pathlib import Path

import numpy as np

# -- the wire frame layout (repro.runtime.wire) --------------------------------
MAGIC = b"ML"
HEAD = struct.Struct(">2sBII")            # magic, kind, header_len, payload_len
KIND_REQ, KIND_RES, KIND_REJ, KIND_BYE = 1, 2, 3, 4
MAX_FRAME = 256 * 1024 * 1024

# Outcomes: a response, a typed rejection, none within the grace period, or
# none because the server closed the connection the request was sent on.
OK, REJECTED, TIMEOUT, DROPPED = "ok", "rejected", "timeout", "dropped"


def encode_request(rid: str, tenant: str, images: np.ndarray) -> bytes:
    images = np.ascontiguousarray(images, np.float32)
    header = json.dumps({
        "rid": rid, "tenant": tenant, "lane": "rows", "deliver": "tokens",
        "priority": 0, "deadline_ms": None, "age_ms": 0.0, "metadata": {},
        "dtype": "float32", "shape": list(images.shape),
    }, separators=(",", ":")).encode()
    body = images.tobytes()
    return HEAD.pack(MAGIC, KIND_REQ, len(header), len(body)) + header + body


def encode_bye() -> bytes:
    header = b'{"reason":"done"}'
    return HEAD.pack(MAGIC, KIND_BYE, len(header), 0) + header


async def read_frame(reader: asyncio.StreamReader):
    """One frame as ``(kind, header, payload bytes)``; None at a clean EOF."""
    try:
        head = await reader.readexactly(HEAD.size)
    except asyncio.IncompleteReadError as e:
        if not e.partial:
            return None
        raise
    magic, kind, hlen, plen = HEAD.unpack(head)
    if magic != MAGIC or hlen + plen > MAX_FRAME:
        raise ValueError(f"bad frame head: magic {magic!r}, {hlen + plen} bytes")
    header = json.loads(await reader.readexactly(hlen))
    payload = await reader.readexactly(plen) if plen else b""
    return kind, header, payload


def decode_array(header: dict, payload: bytes) -> np.ndarray:
    return np.frombuffer(payload, np.dtype(header["dtype"])).reshape(
        header["shape"]
    ).copy()


# -- the traffic, drawn from the seed ------------------------------------------
def zipf_weights(n: int, theta: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** theta
    return w / w.sum()


def open_schedule(spec: dict, seed: int) -> dict:
    """Due times (seconds from the window's opening), tenants and image
    offsets of every request of an open-loop window, drawn from ``seed``."""
    rng = np.random.default_rng([seed, 1])
    rate, seconds = float(spec["rate_per_s"]), float(spec["seconds"])
    n = int(rate * seconds * 1.5) + 64
    due = np.cumsum(rng.exponential(1.0 / rate, size=n))
    due = due[due < seconds]
    pop = spec["popularity"]
    if pop["kind"] != "zipf":
        raise ValueError(f"open loop needs a zipf popularity, got {pop}")
    tenants = rng.choice(
        spec["tenants"], size=due.size, p=zipf_weights(spec["tenants"],
                                                       float(pop["theta"]))
    )
    offsets = rng.integers(
        0, spec["pool_images"] - spec["images_per_request"] + 1, size=due.size
    )
    return {"due": due, "tenant": tenants, "offset": offsets}


def image_pool(spec: dict, seed: int) -> np.ndarray:
    rng = np.random.default_rng([seed, 0])
    c, m = spec["channels"], spec["image_size"]
    return rng.standard_normal((spec["pool_images"], c, m, m), np.float32)


class _Request:
    __slots__ = ("idx", "tenant", "offset", "due", "sent", "done", "outcome",
                 "ev")

    def __init__(self, idx: int, tenant: int, offset: int, due: float):
        self.idx, self.tenant, self.offset, self.due = idx, tenant, offset, due
        self.sent = self.done = None
        self.outcome = None
        self.ev = asyncio.Event()

    def resolve(self, outcome: str, at: float | None) -> None:
        if self.outcome is None:
            self.outcome, self.done = outcome, at
            self.ev.set()


class LoadGen:
    """One window of one traffic mix against ``host:port``."""

    def __init__(self, spec: dict, seed: int, host: str, port: int):
        self.spec, self.seed = spec, seed
        self.host, self.port = host, port
        self.images = int(spec["images_per_request"])
        self.pool = image_pool(spec, seed)
        self.reqs: dict[str, _Request] = {}
        self.t0 = 0.0
        self.closed_at = None
        # A reservoir per tenant of the requests served ok: a uniform sample,
        # drawn from the seed, of at most check_per_tenant of each tenant's.
        self._res_rng = np.random.default_rng([seed, 2])
        self._res: dict[int, tuple[int, list]] = {}
        self._offset_rng = np.random.default_rng([seed, 3])
        self._conns: list = []
        self._open: list[set] = []          # per connection: requests unanswered
        self._readers: list[asyncio.Task] = []

    # -- connections ---------------------------------------------------------
    async def connect(self, n: int) -> None:
        for _ in range(n):
            reader, writer = await asyncio.open_connection(
                self.host, self.port, limit=2 ** 24
            )
            self._readers.append(asyncio.ensure_future(
                self._read_loop(len(self._conns), reader)
            ))
            self._conns.append(writer)
            self._open.append(set())

    def _drop(self, conn: int) -> None:
        """The connection is gone: what was sent on it gets no answer."""
        self._conns[conn] = None
        for r in list(self._open[conn]):
            r.resolve(DROPPED, None)
        self._open[conn].clear()

    async def _read_loop(self, conn: int, reader) -> None:
        try:
            while True:
                frame = await read_frame(reader)
                if frame is None:
                    break
                kind, header, payload = frame
                now = time.monotonic() - self.t0
                if kind == KIND_BYE:
                    break
                r = self.reqs.get(header.get("rid"))
                if r is None or r.outcome is not None:
                    continue
                self._open[conn].discard(r)
                if kind == KIND_RES:
                    self._keep(r, header, payload)
                    r.resolve(OK, now)
                else:
                    r.resolve(f"{REJECTED}:{header.get('code')}", now)
        except (ConnectionError, OSError, ValueError, asyncio.IncompleteReadError):
            pass
        self._drop(conn)

    def _keep(self, r: _Request, header: dict, payload: bytes) -> None:
        if r.due >= self.spec["seconds"]:
            return
        k = int(self.spec["check_per_tenant"])
        seen, kept = self._res.get(r.tenant, (0, []))
        self._res[r.tenant] = (seen + 1, kept)
        if len(kept) < k:
            kept.append((r, decode_array(header, payload)))
            return
        j = int(self._res_rng.integers(0, seen + 1))
        if j < k:
            kept[j] = (r, decode_array(header, payload))

    @property
    def sample(self) -> list[tuple[_Request, np.ndarray]]:
        return sorted((s for _, kept in self._res.values() for s in kept),
                      key=lambda s: s[0].idx)

    async def _send(self, conn: int, r: _Request) -> None:
        frame = encode_request(
            str(r.idx), f"tenant-{r.tenant}",
            self.pool[r.offset : r.offset + self.images],
        )
        r.sent = time.monotonic() - self.t0
        writer = self._conns[conn]
        if writer is None:
            r.resolve(DROPPED, None)
            return
        self._open[conn].add(r)
        try:
            writer.write(frame)
            await writer.drain()
        except (ConnectionError, OSError):
            self._drop(conn)

    # -- the two loops -------------------------------------------------------
    async def run_open(self) -> None:
        sch = open_schedule(self.spec, self.seed)
        n_conn = len(self._conns)
        for i, (due, tenant, off) in enumerate(
            zip(sch["due"], sch["tenant"], sch["offset"])
        ):
            r = _Request(i, int(tenant), int(off), float(due))
            self.reqs[str(i)] = r
            delay = self.t0 + r.due - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            await self._send(i % n_conn, r)
        left = self.t0 + self.spec["seconds"] - time.monotonic()
        if left > 0:
            await asyncio.sleep(left)

    async def run_closed(self) -> None:
        seconds = float(self.spec["seconds"])
        counter = itertools.count()
        hi = self.spec["pool_images"] - self.images + 1

        async def job(j: int) -> None:
            inflight: list[_Request] = []
            while time.monotonic() - self.t0 < seconds:
                if self._conns[j] is None:
                    return              # the server closed this job's connection
                while len(inflight) < self.spec["depth"]:
                    now = time.monotonic() - self.t0
                    if now >= seconds:
                        break           # the window closed while sending
                    r = _Request(next(counter), j,
                                 int(self._offset_rng.integers(0, hi)), now)
                    self.reqs[str(r.idx)] = r
                    inflight.append(r)
                    await self._send(j, r)
                left = self.t0 + seconds - time.monotonic()
                if not inflight or left <= 0:
                    return
                _, pending = await asyncio.wait(
                    [asyncio.ensure_future(r.ev.wait()) for r in inflight],
                    timeout=left,
                    return_when=asyncio.FIRST_COMPLETED,
                )
                for t in pending:
                    t.cancel()
                inflight = [r for r in inflight if r.outcome is None]

        await asyncio.gather(*(job(j) for j in range(self.spec["jobs"])))

    async def finish(self, grace_s: float) -> None:
        """Wait for every issued request's outcome, at most ``grace_s``."""
        waiting = [r.ev.wait() for r in self.reqs.values() if r.outcome is None]
        if waiting:
            try:
                await asyncio.wait_for(asyncio.gather(*waiting), grace_s)
            except asyncio.TimeoutError:
                pass
        for r in self.reqs.values():
            r.resolve(TIMEOUT, None)
        for w in self._conns:
            if w is None:
                continue
            try:
                w.write(encode_bye())
                await w.drain()
                w.close()
            except (ConnectionError, OSError):
                pass
        for t in self._readers:
            t.cancel()
        await asyncio.gather(*self._readers, return_exceptions=True)

    def write(self, out: Path) -> None:
        rows = [
            [r.due, r.sent, r.done, r.outcome, self.images, r.tenant]
            for r in sorted(self.reqs.values(), key=lambda r: r.idx)
        ]
        (out / "requests.json").write_text(json.dumps({
            "seconds": self.spec["seconds"],
            "closed_at": self.closed_at,
            "columns": ["due", "sent", "done", "outcome", "images", "tenant"],
            "rows": rows,
        }))
        sample = self.sample
        np.savez(
            out / "sample.npz",
            idx=np.array([r.idx for r, _ in sample], np.int64),
            tenant=np.array([r.tenant for r, _ in sample], np.int64),
            images=np.stack([
                self.pool[r.offset : r.offset + self.images] for r, _ in sample
            ]) if sample else np.zeros((0,), np.float32),
            served=np.stack([p for _, p in sample])
            if sample else np.zeros((0,), np.float32),
        )


async def _amain(args) -> int:
    spec = json.loads(Path(args.spec).read_text())
    gen = LoadGen(spec, args.seed, args.host, args.port)
    closed = spec["loop"] == "closed"
    await gen.connect(spec["jobs"] if closed else spec["connections"])
    loop = asyncio.get_running_loop()
    reader = asyncio.StreamReader()
    await loop.connect_read_pipe(
        lambda: asyncio.StreamReaderProtocol(reader), sys.stdin
    )
    print("ready", flush=True)
    if (await reader.readline()).strip() != b"go":
        return 2
    gen.t0 = time.monotonic()
    await (gen.run_closed() if closed else gen.run_open())
    gen.closed_at = time.monotonic() - gen.t0
    print("closed", flush=True)
    await gen.finish(float(spec.get("grace_s", 60.0)))
    gen.write(Path(args.out))
    print("done", flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--spec", required=True,
                    help="JSON file: the traffic mix with the cell's "
                         "geometry, tenants and window length filled in")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True, help="directory for the results")
    args = ap.parse_args(argv)
    return asyncio.run(_amain(args))


if __name__ == "__main__":
    sys.exit(main())
