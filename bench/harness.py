"""The benchmark harness: one cell, one seed, one measured window.

Everything that belongs to a configuration, a traffic mix or a metric is a
file the harness finds by name, from ``BENCHMARK.json`` at the root:

* ``bench/configs/<config>.json`` (the workload's ``file``): geometry,
  tenants, slot capacity, extra front-door flags, the limits of the check;
* ``bench/traffic/<traffic>.json``: the loop, arrivals, request sizes,
  popularity, depth, rate, and how many requests the check samples;
* ``bench/metrics/<metric>.py``: a ``read(run)`` that returns the metric's
  value from the run's record, or None when there is nothing to read.  A
  quantity split by the end-to-end metric it moves (``flush_device_ms.infer``,
  ``flush_device_ms.train``) may share one reader, named by the part before
  the first dot (``flush_device_ms.py``).

A run builds the system through the program's own entry points
(``launch/serve.parse_args`` -> ``launch/server.build_front`` ->
``make_server``), serves it on a loopback port, warms every microbatch
shape the cell's traffic can reach, and lets ``bench/loadgen.py`` -- a
child process that never imports JAX -- drive it for ``--seconds``.  Once
the window has closed and the program's state is freed, a seeded sample of
what was served is held to the float64 reference (``bench/reference.py``).
"""
from __future__ import annotations

import asyncio
import dataclasses
import gc
import importlib.util
import json
import resource
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np

from bench import reference, trace as tr, work

ROOT = Path(__file__).resolve().parents[1]
LOADGEN = Path(__file__).resolve().parent / "loadgen.py"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# -- the cell and its files -----------------------------------------------------
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: Path, name: str) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=json.loads((root / cfg_entry["file"]).read_text()),
        traffic=json.loads(
            (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text()
        ),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)],
    )


def load_reader(root: Path, metric: str):
    path = root / "bench" / "metrics" / f"{metric}.py"
    if not path.exists():
        path = path.with_name(f"{metric.split('.')[0]}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


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


def loadgen_spec(cell: Cell, seconds: float) -> dict:
    g, t = cell.config["geometry"], dict(cell.traffic)
    t.update(seconds=seconds, tenants=cell.config["tenants"],
             channels=g["alpha"], image_size=g["m"])
    if t.get("jobs") == "one_per_tenant":
        t["jobs"] = cell.config["tenants"]
    return t


# -- compile accounting ----------------------------------------------------------
class Compiles:
    """Counts XLA compiles and persistent-cache loads as JAX reports them."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"

    def __init__(self):
        self.events: list[tuple[str, float, float]] = []   # (kind, at, secs)

    def __call__(self, event: str, duration: float, **_) -> None:
        if event in (self.COMPILE, self.LOAD):
            self.events.append((event, time.perf_counter(), duration))

    def between(self, lo: float, hi: float) -> dict:
        out = {"compiles": 0, "compile_s": 0.0, "loads": 0, "load_s": 0.0}
        for kind, at, secs in self.events:
            if lo <= at < hi:
                key = "compile" if kind == self.COMPILE else "load"
                out[key + "s"] += 1
                out[key + "_s"] += secs
        return out


class Pauses:
    """What holds the serving process still in the window: each garbage
    collection (``gc.callbacks``), and each time a thread that sleeps 10 ms
    at a stretch wakes more than 50 ms late (the interpreter lock held, or
    the process not scheduled).  Reported on standard error, with the
    process's resource use over the window."""

    TICK_S, LATE_S = 0.010, 0.050

    def __init__(self):
        self.gcs: list[tuple[int, float, float]] = []   # (generation, at, secs)
        self.late: list[tuple[float, float]] = []       # (at, secs late)
        self._gc_at = None
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._watch, daemon=True)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_at = time.perf_counter()
        elif self._gc_at is not None:
            self.gcs.append((info["generation"], self._gc_at,
                             time.perf_counter() - self._gc_at))

    def _watch(self) -> None:
        while not self._stop.is_set():
            t = time.perf_counter()
            time.sleep(self.TICK_S)
            late = time.perf_counter() - t - self.TICK_S
            if late > self.LATE_S:
                self.late.append((t, late))

    def start(self) -> None:
        self.t0, self.usage0 = time.perf_counter(), resource.getrusage(
            resource.RUSAGE_SELF)
        gc.callbacks.append(self._on_gc)
        self._thread.start()

    def stop(self) -> str:
        self._stop.set()
        self._thread.join()
        gc.callbacks.remove(self._on_gc)
        u, u0 = resource.getrusage(resource.RUSAGE_SELF), self.usage0
        gen = [[d for g, _, d in self.gcs if g == k] for k in range(3)]
        worst_gc = sorted(self.gcs, key=lambda e: -e[2])[:3]
        worst_late = sorted(self.late, key=lambda e: -e[1])[:3]
        return (
            "pauses: gc " + ", ".join(
                f"gen{k} {len(d)} x (total {sum(d):.3f} s, max "
                f"{max(d, default=0.0) * 1e3:.1f} ms)" for k, d in enumerate(gen))
            + "; longest gc " + ", ".join(
                f"gen{g} {d * 1e3:.1f} ms at {at - self.t0:.2f} s"
                for g, at, d in worst_gc)
            + f"; woke over {self.LATE_S * 1e3:.0f} ms late {len(self.late)} x, "
            + "longest " + ", ".join(f"{d * 1e3:.1f} ms at {at - self.t0:.2f} s"
                                     for at, d in worst_late)
            + f"; process user {u.ru_utime - u0.ru_utime:.2f} s, system "
            f"{u.ru_stime - u0.ru_stime:.2f} s, minor faults "
            f"{u.ru_minflt - u0.ru_minflt}, major {u.ru_majflt - u0.ru_majflt}, "
            f"involuntary switches {u.ru_nivcsw - u0.ru_nivcsw}, max resident "
            f"{u.ru_maxrss / 2 ** 20:.2f} GiB"
        )


def reachable_shapes(engine, cell: Cell) -> list[tuple[int, int]]:
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


# -- spans around the program's layers (traced runs only) -------------------------
def instrument(front, server, steps: list) -> None:
    """Open a ``bench.*`` host span around each call into the flush phases
    and the server's request and completion paths, and log each vision
    microbatch's work as the device phase dispatches it."""
    from jax.profiler import TraceAnnotation

    def wrap(obj, name: str, span: str, note=None):
        fn = getattr(obj, name)

        def wrapped(*a, **k):
            if note is not None:
                note(*a)
            with TraceAnnotation(span):
                return fn(*a, **k)

        setattr(obj, name, wrapped)

    def note(flush_work):
        now = time.perf_counter()
        for item in flush_work.items:
            if item.lane != "vision":
                continue
            mb = item.mb
            slots = {int(mb.group_tenant[s.group]) for s in mb.slices}
            steps.append((now, sum(s.n_rows for s in mb.slices), len(slots),
                          *mb.x.shape[:2]))

    eng = front.engine
    wrap(eng, "begin_flush", "bench.flush.coalesce")
    wrap(eng, "execute_flush", "bench.flush.device", note)
    wrap(eng, "publish_flush", "bench.flush.publish")
    wrap(server, "_on_request", "bench.server.request")
    wrap(server, "_complete", "bench.server.complete")


def snapshot_stats(stats) -> dict:
    from repro.runtime.engine import FLUSH_PHASES

    return {
        "requests": stats.requests, "rows_in": stats.rows_in,
        "rows_padded": stats.rows_padded, "microbatches": stats.microbatches,
        "flushes": stats.flushes, "shed_requests": stats.shed_requests,
        "flush_failures": stats.flush_failures,
        "phase_ms": {p: list(stats._phases_ms[p]) for p in FLUSH_PHASES},
        "submit_wait_ms": list(stats._submit_wait_ms),
    }


# -- the window ---------------------------------------------------------------------
async def _serve_window(front, args, spec_path: Path, out_dir: Path, seed: int,
                        seconds: float, hooks) -> int:
    from repro.launch.server import make_server

    server = make_server(front, args)
    await server.start()
    hooks.server(server)
    proc = await asyncio.create_subprocess_exec(
        sys.executable, str(LOADGEN), "--port", str(server.port),
        "--spec", str(spec_path), "--seed", str(seed), "--out", str(out_dir),
        stdin=asyncio.subprocess.PIPE, stdout=asyncio.subprocess.PIPE,
    )

    async def expect(word: bytes, timeout: float) -> None:
        line = await asyncio.wait_for(proc.stdout.readline(), timeout)
        if line.strip() != word:
            raise RuntimeError(f"load generator said {line!r}, expected {word!r}")

    try:
        await expect(b"ready", 300.0)
        hooks.go()
        proc.stdin.write(b"go\n")
        await proc.stdin.drain()
        await expect(b"closed", seconds + 120.0)
        await hooks.closed()
        await expect(b"done", 180.0)
        rc = await asyncio.wait_for(proc.wait(), 60.0)
        if rc:
            raise RuntimeError(f"load generator exited {rc}")
    finally:
        if proc.returncode is None:
            proc.kill()
            await proc.wait()
        lost = await server.drain_and_stop(timeout=60.0)
    return lost


def _requests(out_dir: Path) -> SimpleNamespace:
    doc = json.loads((out_dir / "requests.json").read_text())
    rows = doc["rows"]
    col = {c: i for i, c in enumerate(doc["columns"])}

    def arr(name, dtype=np.float64):
        return np.array([np.nan if r[col[name]] is None else r[col[name]]
                         for r in rows], dtype)

    outcome = [r[col["outcome"]] for r in rows]
    return SimpleNamespace(
        due=arr("due"), sent=arr("sent"), done=arr("done"),
        images=arr("images", np.int64), tenant=arr("tenant", np.int64),
        ok=np.array([o == "ok" for o in outcome], bool),
        lost=np.array([o in ("timeout", "dropped") for o in outcome], bool),
        outcome=outcome, closed_at=doc["closed_at"],
    )


def _counts(outcomes) -> dict:
    names, counts = np.unique(np.asarray(outcomes, str), return_counts=True)
    return {str(n): int(c) for n, c in zip(names, counts)}


def _quantile_ms(xs, q: float) -> float:
    xs = np.sort(np.asarray(xs, np.float64))
    if not xs.size:
        return float("nan")
    return float(xs[max(0, int(np.ceil(q * xs.size)) - 1)]) * 1e3


class System:
    """The cell's front door, built through the program's entry points and
    warmed at every shape its traffic can reach; serves one or more windows.
    """

    def __init__(self, cell: Cell, seed: int):
        import jax

        from repro.launch.serve import parse_args
        from repro.launch.server import build_front

        self.cell = cell
        self.compiles = Compiles()
        jax.monitoring.register_event_duration_secs_listener(self.compiles)
        self.t_import = time.perf_counter()
        self.args = parse_args(serve_flags(cell.config, seed))
        self.front = build_front(self.args)
        self.t_built = time.perf_counter()
        self.shapes = reachable_shapes(self.front.engine, cell)
        warm(self.front.engine, self.shapes)
        self.t_warm = time.perf_counter()

    def close(self) -> int:
        """Stop the front door; non-zero when it lost work or its flusher."""
        import jax

        from repro.launch.server import close_front

        jax.monitoring.unregister_event_duration_listener(self.compiles)
        front, self.front = self.front, None
        return close_front(front, 0)

    def window(self, out: Path, seconds: float, seed: int, traced: bool,
               traffic: dict | None = None) -> SimpleNamespace:
        """Serve one window of the cell's traffic (or ``traffic``), drawn from
        ``seed``; the load generator's results land in ``out``."""
        import jax

        from repro.runtime import EngineStats, delivery_trace_count

        front, engine = self.front, self.front.engine
        cell = dataclasses.replace(self.cell, traffic=traffic or self.cell.traffic)
        spec = loadgen_spec(cell, seconds)
        out.mkdir(parents=True, exist_ok=True)
        (out / "spec.json").write_text(json.dumps(spec))
        w = SimpleNamespace(spec=spec, steps=[], trace_dir=out / "trace")
        span, pauses = [], Pauses()

        class Hooks:
            def server(self, server):
                if traced:
                    instrument(front, server, w.steps)

            def go(self):
                with front._cv:
                    engine.stats = EngineStats()
                    engine.stats.service_share_fn = engine.scheduler.service_share
                if traced:
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0
                    jax.profiler.start_trace(str(w.trace_dir),
                                             profiler_options=opts)
                    span.append(jax.profiler.TraceAnnotation(tr.WINDOW_SPAN))
                    span[0].__enter__()
                w.traces = delivery_trace_count()
                pauses.start()
                w.go = time.perf_counter()

            async def closed(self):
                w.closed = time.perf_counter()
                w.pauses = pauses.stop()
                w.traces = delivery_trace_count() - w.traces
                with front._cv:
                    w.stats = snapshot_stats(engine.stats)
                if traced:
                    span[0].__exit__(None, None, None)
                    await asyncio.get_running_loop().run_in_executor(
                        None, jax.profiler.stop_trace
                    )

        w.lost_server = asyncio.run(_serve_window(
            front, self.args, out / "spec.json", out, seed, seconds, Hooks()
        ))
        w.req = _requests(out)
        with np.load(out / "sample.npz") as z:
            w.sample = {k: z[k] for k in z.files}
        due_in = w.req.due < seconds
        w.attempted = int(due_in.sum())
        w.ok = int((w.req.ok & due_in).sum())
        w.late = (w.req.sent - w.req.due)[due_in & ~np.isnan(w.req.sent)]
        w.inside = self.compiles.between(w.go, w.closed)
        log(f"window: {seconds:g} s, attempted {w.attempted}, ok {w.ok}, "
            f"outcomes {_counts(w.req.outcome)}; generator lateness p50 "
            f"{_quantile_ms(w.late, 0.5):.3f} ms, p99 "
            f"{_quantile_ms(w.late, 0.99):.3f} ms, max "
            f"{(w.late.max() * 1e3 if w.late.size else 0.0):.3f} ms; in the "
            f"window {w.inside['compiles']} compiles, {w.inside['loads']} "
            f"cache loads, {w.traces} traces of the delivery steps")
        log(w.pauses)
        return w

    def permutations(self) -> dict[int, np.ndarray]:
        """Each tenant's secret channel permutation, from its provider's
        record (the front door registers tenant ``i`` as ``tenant-i``)."""
        reg = self.front.engine.registry
        return {i: np.array(reg.session(f"tenant-{i}").provider._perm)
                for i in range(self.cell.config["tenants"])}


def find_devices(cell: Cell, require_tpu: bool):
    """The devices the cell runs on, or None (with the reason on standard
    error) when JAX finds no TPU or too few chips."""
    import jax

    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    # Cache every program, however quickly it compiled, so that only a
    # checkout's first run compiles.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if not require_tpu:
        return devices
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        log(f"refused: JAX found {len(devices)} {devices[0].platform} "
            f"device(s); {cell.name} needs {cell.chips} TPU chip(s)")
        return None
    from repro.kernels.dispatch import resolve_backend

    if resolve_backend(None) != "pallas":
        log(f"refused: the kernel backend resolves to "
            f"{resolve_backend(None)!r}, not 'pallas'")
        return None
    return devices


# -- one run --------------------------------------------------------------------------
def run(root: Path, workload: str, seed: int, seconds: float, traced: bool,
        *, started: float, require_tpu: bool = True) -> dict | None:
    """Run one cell once and return its result line (None: refused)."""
    cell = load_cell(root, workload)
    devices = find_devices(cell, require_tpu)
    if devices is None:
        return None
    geom = dict(cell.config["geometry"])
    tmp = Path(tempfile.mkdtemp(prefix="bench-"))
    try:
        system = System(cell, seed)
        try:
            w = system.window(tmp, seconds, seed, traced)
            perms = system.permutations()
        finally:
            rc_front = system.close()
        setup_s = w.go - started
        setup = system.compiles.between(started, w.go)
        log(f"setup: {setup_s:.3f} s to the window: imports "
            f"{system.t_import - started:.3f} s, build_front "
            f"{system.t_built - system.t_import:.3f} s (registration, uploads "
            f"and its warm flush), {len(system.shapes)} bucket warm-ups "
            f"{system.t_warm - system.t_built:.3f} s, load generator "
            f"{w.go - system.t_warm:.3f} s; of all that, {setup['compiles']} "
            f"compiles took {setup['compile_s']:.3f} s and {setup['loads']} "
            f"cache loads {setup['load_s']:.3f} s")
        memory_peak = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in devices[: cell.chips]
        )
        del system
        gc.collect()

        # The check, on the host, once the program's state is gone.
        lost = int(w.req.lost.sum()) + int(w.lost_server) + int(rc_front != 0)
        kernels = reference.developer_kernels(geom, cell.config["tenants"], seed)
        cmp = reference.compare(w.sample, geom, kernels, perms)
        tenants = cell.config["tenants"]
        sample = tenants * w.spec["check_per_tenant"] * w.spec["images_per_request"]
        # (name, value, limit, the value has to be at least the limit)
        checks = [
            ("max_abs_err", cmp["max_abs_err"],
             cell.config["limits"]["max_abs_err"], False),
            ("unpermuted_tenants", reference.unpermuted(perms, geom["beta"]), 0,
             False),
            ("lost", lost, 0, False),
            ("compared_tenants", cmp["compared_tenants"], tenants, True),
            ("compared_images", cmp["compared_images"], sample, True),
        ]
        correct = all(v >= lim if least else v <= lim
                      for _, v, lim, least in checks)

        dev = devices[0]
        record = SimpleNamespace(
            cell=cell, seconds=seconds, grace_s=float(w.spec.get("grace_s", 60.0)),
            req=w.req, setup_s=setup_s, memory_peak_bytes=memory_peak,
            stats=w.stats, geom=geom, trace=None,
        )
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(devices), "memory_peak_bytes": int(memory_peak)}
        if traced:
            record.trace = reduce_trace(root, w, dev)
            device["busy_s"] = record.trace["busy_s"]
            device["window_s"] = record.trace["window_s"]
        metrics = {}
        for m in cell.per_layer if traced else cell.end_to_end:
            value = load_reader(root, m["name"])(record)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        result = {"correct": bool(correct), "attempted": w.attempted,
                  "failed": w.attempted - w.ok, "metrics": metrics,
                  "device": device}
        if traced:
            result["breakdown"] = record.trace["breakdown"]
        result["check"] = {name: {"value": v, "limit": lim}
                           for name, v, lim, _ in checks}
        for name, v, lim, least in checks:
            log(f"check: {name} {v!r} ({'at least' if least else 'at most'} "
                f"{lim!r})")
        return result
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def reduce_trace(root: Path, w: SimpleNamespace, dev) -> dict:
    """The traced window for the metric readers: the plain trace and the
    window's bounds, busy and window seconds, the work of each delivery step
    dispatched in the window, the chip's peaks, and the breakdown."""
    files = sorted(w.trace_dir.rglob("*.xplane.pb"))
    if not files:
        raise RuntimeError("the profiler wrote no trace")
    trace = tr.load(files[-1])
    win = tr.window(trace)
    if win is None:
        raise RuntimeError("the trace holds no bench.window span")
    on_device = bool(tr.device_planes(trace))
    log(f"trace: {sum(len(ln['events']) for p in trace['planes'] for ln in p['lines'])} "
        f"events on planes {[p['name'] for p in trace['planes']]}")
    return {
        "plain": trace,
        "win": win,
        "window_s": (win[1] - win[0]) / 1e9,
        "busy_s": tr.busy_s(trace, win),
        "work": [s for s in w.steps if w.go <= s[0] < w.closed],
        "peak": work.peak_of(dev.device_kind, root) if on_device else None,
        "breakdown": {"device_ops": tr.top_ops(trace, win),
                      "idle_gaps": tr.idle_gaps(trace, win)},
    }
