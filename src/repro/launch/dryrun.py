import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: AOT lower + compile every (arch x shape x mesh) cell.

MUST be run as its own process (``python -m repro.launch.dryrun``): the first
two lines force 512 host platform devices BEFORE jax initializes.  Smoke tests
and benchmarks import repro normally and see 1 device.

Per cell:
  * build abstract params / optimizer state / caches / batch (ShapeDtypeStruct
    only — no allocation), with NamedShardings from repro.sharding.rules;
  * jit(step, in_shardings, out_shardings).lower(...).compile();
  * record memory_analysis(), cost_analysis(), and the collective-op byte
    volumes parsed from the compiled HLO;
  * write artifacts/dryrun/<mesh>/<arch>__<shape>.json.

Exit code is non-zero if any requested cell fails.
"""
import argparse
import json
import re
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import (
    ARCHS, SHAPES, get_config, input_specs, skip_reason,
)
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import TrainHParams, make_decode_step, make_prefill_step, make_train_step
from repro.models.api import Model
from repro.models.base import param_axes
from repro.optim import adamw
from repro.sharding import rules as R

ARTIFACTS = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)
_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1,
}
_SHAPE_RE = re.compile(r"\b(f64|f32|bf16|f16|s64|u64|s32|u32|s16|u16|s8|u8|pred)\[([0-9,]*)\]")


def _shape_bytes(m: re.Match) -> int:
    dt, dims = m.group(1), m.group(2)
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


def parse_collectives(hlo_text: str) -> dict:
    """Sum operand + result bytes of collective ops in compiled HLO."""
    out = {k: {"count": 0, "operand_bytes": 0, "result_bytes": 0} for k in _COLLECTIVES}
    start_re = re.compile(
        r"=\s*(?:\([^)]*\)|\S+)\s+(" + "|".join(_COLLECTIVES) + r")(?:-start|-done)?\("
    )
    for line in hlo_text.splitlines():
        mm = start_re.search(line)
        if not mm:
            continue
        if "-done(" in line:
            continue  # avoid double counting async start/done pairs
        kind = mm.group(1)
        _, _, rhs = line.partition("=")
        # result shapes appear between '=' and the op name; operands after '('
        head = rhs[: rhs.find("(")]
        tail = rhs[rhs.find("(") :]
        res = sum(_shape_bytes(m) for m in _SHAPE_RE.finditer(head))
        opd = sum(_shape_bytes(m) for m in _SHAPE_RE.finditer(tail))
        out[kind]["count"] += 1
        out[kind]["operand_bytes"] += opd
        out[kind]["result_bytes"] += res
    out["total_operand_bytes"] = sum(v["operand_bytes"] for k, v in out.items() if isinstance(v, dict))
    out["total_result_bytes"] = sum(v["result_bytes"] for k, v in out.items() if isinstance(v, dict))
    return out


def _named(mesh, tree_specs):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s) if isinstance(s, P) else s, tree_specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def batch_shardings(mesh, specs: dict) -> dict:
    dp = R.dp_axes(mesh)
    out = {}
    for k, v in specs.items():
        if v.ndim == 0:
            out[k] = NamedSharding(mesh, P())
        else:
            b = v.shape[0]
            lead = dp if (dp and b % R.Rules({}, mesh).axis_size(dp) == 0) else None
            out[k] = NamedSharding(mesh, P(lead, *([None] * (v.ndim - 1))))
    return out


def microbatches(cfg, shape, mesh) -> int:
    # per-device microbatch target: sized so remat'd activations fit HBM.
    # With fused CE ((B,S,V) logits never materialize) the larger targets for
    # mid-size models halve per-step parameter re-reads (§Perf iteration 2:
    # baseline used target=1 for everything >= 2048).
    dp = R.Rules({}, mesh).axis_size(R.dp_axes(mesh))
    per_dev = shape.global_batch // dp
    if cfg.d_model >= 4096:
        target = 1
    elif cfg.d_model >= 2048:
        target = min(4, per_dev)
    else:
        target = min(8, per_dev)
    n = max(1, per_dev // max(target, 1))
    while shape.global_batch % n or (shape.global_batch // n) % dp:
        n -= 1
    return n


def analysis_cfg(cfg, n_groups: int, shape):
    """Variant for exact cost accounting: XLA:CPU cost_analysis counts while
    bodies once, so we unroll all scans.  Layer count is reduced to
    ``n_groups`` (lowered twice, g=1 and g=2, then linearly extrapolated:
    total = f(1) + (G-1) (f(2)-f(1)) — exact because groups are homogeneous).
    Inner loops are removed: attention goes dense (same masked-S^2 flop count
    as the production flash-scan), rwkv runs one full-sequence chunk."""
    import dataclasses
    kw: dict = dict(
        n_groups=n_groups, scan_unroll=True, dense_attn_max_seq=1 << 30,
    )
    # rwkv's chunk scan honours cfg.scan_unroll directly, so the production
    # chunking is measured as-is (an earlier chunk=seq_len stand-in inflated
    # the baseline — see §Perf H3 validation note).
    if cfg.frontend is not None and cfg.frontend.enc_layers:
        kw["frontend"] = dataclasses.replace(cfg.frontend, enc_layers=n_groups)
    return dataclasses.replace(cfg, **kw)


# §Perf strategy (EXPERIMENTS.md): decode steps drop FSDP — an FSDP'd decode
# all-gathers every weight per generated token (measured: 97% of command-r
# decode collective bytes).  TP-only params fit HBM for every arch except the
# 90B VLM (11 GB params + 5.4 GB KV > 16 GB), which keeps FSDP.
DECODE_KEEPS_FSDP = {"llama32_vision_90b"}


def build_cell(arch: str, shape_name: str, multi_pod: bool,
               fsdp: bool | None = None,
               cfg_override=None, single_micro: bool = False):
    """Returns (jitted, abstract_args) ready to lower."""
    cfg = cfg_override if cfg_override is not None else get_config(arch)
    shape = SHAPES[shape_name]
    if fsdp is None:
        fsdp = not (shape.kind == "decode" and arch not in DECODE_KEEPS_FSDP)
    mesh = make_production_mesh(multi_pod=multi_pod)
    model = Model(cfg)
    fallbacks: list[str] = []

    prules = R.param_rules(mesh, fsdp=fsdp)
    p_abs = model.abstract_params()
    p_sh = jax.tree.map(
        lambda ax, ab: prules.sharding_for(ax, ab.shape, fallbacks),
        model.axes(), p_abs,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x),
    )
    specs = input_specs(cfg, shape)
    b_sh = batch_shardings(mesh, specs)

    if shape.kind == "train":
        hp = TrainHParams(
            microbatch=1 if single_micro else microbatches(cfg, shape, mesh)
        )
        step = make_train_step(model, hp)
        orules = R.opt_state_rules(mesh)
        o_abs = jax.eval_shape(adamw.init_state, p_abs)
        o_sh = {
            "m": jax.tree.map(
                lambda ax, ab: orules.sharding_for(ax, ab.shape, fallbacks),
                model.axes(), o_abs["m"],
                is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x),
            ),
            "v": jax.tree.map(
                lambda ax, ab: orules.sharding_for(ax, ab.shape, fallbacks),
                model.axes(), o_abs["v"],
                is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x),
            ),
            "count": NamedSharding(mesh, P()),
        }
        jitted = jax.jit(
            step,
            in_shardings=(p_sh, o_sh, b_sh),
            out_shardings=(p_sh, o_sh, None),
        )
        args = (p_abs, o_abs, specs)
        extra = {"microbatch": hp.microbatch}
    else:
        seq_shard = (cfg.mla is not None) or (
            cfg.n_kv_heads % mesh.shape["model"] != 0
        )
        crules = R.cache_rules(mesh, seq_shard=seq_shard)
        cache_axes = param_axes(model.cache_schema(shape.global_batch, shape.seq_len))
        c_abs = model.abstract_cache(shape.global_batch, shape.seq_len)
        c_sh = jax.tree.map(
            lambda ax, ab: crules.sharding_for(ax, ab.shape, fallbacks),
            cache_axes, c_abs,
            is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x),
        )
        if shape.kind == "prefill":
            step = make_prefill_step(model)
            jitted = jax.jit(
                step,
                in_shardings=(p_sh, b_sh, c_sh),
                out_shardings=(None, c_sh),
            )
            args = (p_abs, specs, c_abs)
            extra = {"seq_shard": seq_shard, "fsdp": fsdp}
        else:
            step = make_decode_step(model)
            jitted = jax.jit(
                step,
                in_shardings=(p_sh, b_sh["token"], b_sh["t"], c_sh),
                out_shardings=(None, c_sh),
            )
            args = (p_abs, specs["token"], specs["t"], c_abs)
            extra = {"seq_shard": seq_shard, "fsdp": fsdp}

    return jitted, args, mesh, fallbacks, extra, model


def run_cell(arch: str, shape_name: str, multi_pod: bool, save_hlo: bool = False) -> dict:
    t0 = time.time()
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    reason = skip_reason(cfg, shape)
    mesh_name = "pod2" if multi_pod else "pod1"
    rec: dict = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "status": "skip", "skip_reason": reason,
    }
    if reason is not None:
        return rec

    jitted, args, mesh, fallbacks, extra, model = build_cell(arch, shape_name, multi_pod)
    with jax.set_mesh(mesh):
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    try:
        mem = compiled.memory_analysis()
        mem_d = {
            k: int(getattr(mem, k))
            for k in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "generated_code_size_in_bytes",
                "alias_size_in_bytes",
            )
            if hasattr(mem, k)
        }
        if not mem_d:
            mem_d = {"repr": str(mem)}
    except Exception as e:  # pragma: no cover
        mem_d = {"error": f"{type(e).__name__}: {e}"}
    try:
        cost = dict(compiled.cost_analysis())
        cost = {k: float(v) for k, v in cost.items() if isinstance(v, (int, float))}
    except Exception as e:  # pragma: no cover
        cost = {"error": f"{type(e).__name__}: {e}"}

    hlo = compiled.as_text()
    coll = parse_collectives(hlo)

    rec.update(
        status="ok",
        n_devices=int(mesh.devices.size),
        params=model.param_count(),
        fallbacks=fallbacks,
        extra=extra,
        lower_s=round(t_lower, 1),
        compile_s=round(t_compile, 1),
        memory=mem_d,
        cost=cost,
        collectives=coll,
        hlo_bytes=len(hlo),
    )
    if save_hlo:
        outdir = ARTIFACTS / mesh_name
        outdir.mkdir(parents=True, exist_ok=True)
        (outdir / f"{arch}__{shape_name}.hlo.txt").write_text(hlo)
    return rec


def run_analysis_cell(arch: str, shape_name: str, multi_pod: bool = False) -> dict:
    """Lower unrolled g=1 / g=2 variants; extrapolate exact per-step totals.

    Returns {flops, bytes_accessed, collective bytes by kind} for the FULL
    model at this cell, all per-device (cost_analysis is per-device under
    SPMD).
    """
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    reason = skip_reason(cfg, shape)
    rec: dict = {"arch": arch, "shape": shape_name, "status": "skip",
                 "skip_reason": reason}
    if reason is not None:
        return rec

    f: dict[int, dict] = {}
    for g in (1, 2):
        t0 = time.time()
        acfg = analysis_cfg(cfg, g, shape)
        jitted, args, mesh, _, _, _ = build_cell(
            arch, shape_name, multi_pod, cfg_override=acfg, single_micro=True
        )
        with jax.set_mesh(mesh):
            compiled = jitted.lower(*args).compile()
        cost = {k: float(v) for k, v in dict(compiled.cost_analysis()).items()
                if isinstance(v, (int, float))}
        coll = parse_collectives(compiled.as_text())
        f[g] = {
            "flops": cost.get("flops", 0.0),
            "bytes": cost.get("bytes accessed", 0.0),
            "coll": {k: coll[k]["result_bytes"] for k in _COLLECTIVES},
            "compile_s": round(time.time() - t0, 1),
        }

    G = cfg.n_groups
    n_micro = 1 if shape.kind != "train" else microbatches(
        cfg, shape, make_production_mesh(multi_pod=multi_pod)
    )
    # analysis ran the FULL global batch in one shot -> already per-step total.
    # clamp at the g=1 value: compiler noise can make f(2) < f(1) for rare
    # boundary collectives, which would extrapolate negative.
    def extrap(a, b):
        return max(a, a + (G - 1) * (b - a)) if b < a else a + (G - 1) * (b - a)

    rec.update(
        status="ok",
        n_groups=G,
        microbatch_prod=n_micro,
        flops=extrap(f[1]["flops"], f[2]["flops"]),
        bytes=extrap(f[1]["bytes"], f[2]["bytes"]),
        coll={k: extrap(f[1]["coll"][k], f[2]["coll"][k]) for k in _COLLECTIVES},
        raw=f,
    )
    rec["coll_total"] = sum(rec["coll"].values())
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=ARCHS)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--analysis", action="store_true",
                    help="run the unrolled cost-extrapolation pass instead")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--no-fsdp", action="store_true")
    args = ap.parse_args(argv)

    cells = []
    archs = ARCHS if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    for mp in meshes:
        for a in archs:
            for s in shapes:
                cells.append((a, s, mp))

    failures = 0
    for a, s, mp in cells:
        mesh_name = "pod2" if mp else "pod1"
        kind = "analysis" if args.analysis else "dryrun"
        try:
            if args.analysis:
                rec = run_analysis_cell(a, s, mp)
            else:
                rec = run_cell(a, s, mp, save_hlo=args.save_hlo)
        except Exception as e:
            rec = {
                "arch": a, "shape": s, "mesh": mesh_name, "status": "fail",
                "error": f"{type(e).__name__}: {e}",
                "traceback": traceback.format_exc()[-4000:],
            }
            failures += 1
        outdir = (ARTIFACTS.parent / kind) / mesh_name
        outdir.mkdir(parents=True, exist_ok=True)
        (outdir / f"{a}__{s}.json").write_text(json.dumps(rec, indent=1))
        stat = rec["status"]
        if args.analysis and stat == "ok":
            msg = f"flops {rec['flops']:.3g} bytes {rec['bytes']:.3g} coll {rec['coll_total']:.3g}B"
        else:
            msg = rec.get("skip_reason") or rec.get("error") or (
                f"compile {rec.get('compile_s')}s flops {rec.get('cost', {}).get('flops', 0):.3g} "
                f"coll {rec.get('collectives', {}).get('total_result_bytes', 0):.3g}B"
            )
        print(f"[{mesh_name}] {a:22s} {s:12s} {stat:5s} {msg}", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
