"""Multi-pod dry-run: AOT-lower and compile every (arch × input-shape) on the
production meshes, print memory/cost analysis, and dump roofline artifacts.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch stablelm-3b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multipod] [--out artifacts]

The FIRST TWO LINES below must run before any other import: jax locks the
device count on first init, and the dry-run (only the dry-run) needs 512
placeholder host devices to build the 2×16×16 production mesh.
"""
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

import argparse      # noqa: E402
import json          # noqa: E402
import re            # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402

from repro.configs import ASSIGNED_ARCHS, get_config  # noqa: E402
from repro.launch import mesh as mesh_lib             # noqa: E402
from repro.launch.sharding import build_step, supported  # noqa: E402
from repro.models.config import INPUT_SHAPES          # noqa: E402

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE_RE = re.compile(r"\b(pred|bf16|f16|f32|f64|s8|u8|s16|u16|s32|u32|s64|u64)\[([0-9,]*)\]")


def collective_bytes(hlo_text: str) -> dict:
    """Sum result-shape bytes of every collective op in optimized HLO."""
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if "-done(" in stripped or "-done." in stripped:
            continue
        hit = None
        for op in _COLLECTIVES:
            if re.search(rf"\b{op}(-start)?\(", stripped):
                hit = op
                break
        if hit is None:
            continue
        # result shapes appear on the LHS before the op call
        lhs = stripped.split(f" {hit}", 1)[0]
        nbytes = 0
        for m in _SHAPE_RE.finditer(lhs):
            dt, dims = m.group(1), m.group(2)
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        out[hit] += nbytes
        counts[hit] += 1
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    out["counts"] = counts
    return out


def model_flops(cfg, shape) -> float:
    n_active = cfg.param_count(active_only=True)
    if shape.kind == "train":
        return 6.0 * n_active * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.global_batch * shape.seq_len
    return 2.0 * n_active * shape.global_batch            # decode: 1 token


def _compile_and_measure(cfg, shape, mesh, variant: str = "") -> dict:
    bundle = build_step(cfg, shape, mesh, variant=variant)
    t0 = time.time()
    lowered = bundle.fn.lower(*bundle.args)
    t_lower = time.time() - t0
    t1 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t1
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    return {
        "bundle": bundle, "mem": mem, "hlo": hlo,
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "coll": coll,
        "lower_s": t_lower, "compile_s": t_compile,
    }


def apply_variant(cfg, variant: str, multi_pod: bool):
    """§Perf hillclimb variants (EXPERIMENTS.md §Perf)."""
    import dataclasses
    if not variant or variant == "baseline":
        return cfg
    if variant == "moe_local":
        shards = 32 if multi_pod else 16      # batch-axis size
        return dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, dispatch="local",
                                         local_shards=shards))
    if variant == "mla_absorbed":
        return dataclasses.replace(cfg, mla_absorbed_train=True)
    if variant == "kv_int8":
        return dataclasses.replace(cfg, kv_cache_quant="int8")
    if variant == "kv_replicated":
        return cfg          # rules change, handled in build_decode_step
    if variant == "kv_replicated+int8":
        return dataclasses.replace(cfg, kv_cache_quant="int8")
    if variant == "serve_mesh_32x8":
        return cfg          # mesh change, handled in run_one
    if variant == "serve_mesh_32x8+int8":
        return dataclasses.replace(cfg, kv_cache_quant="int8")
    if variant == "moe_local+mla_absorbed":
        shards = 32 if multi_pod else 16
        return dataclasses.replace(
            cfg, mla_absorbed_train=True,
            moe=dataclasses.replace(cfg.moe, dispatch="local",
                                    local_shards=shards))
    raise KeyError(variant)


def run_one(arch: str, shape_name: str, multi_pod: bool,
            probes: bool = True, cfg=None, variant: str = "") -> dict:
    from repro.launch import roofline as rf
    cfg = cfg or get_config(arch)
    cfg = apply_variant(cfg, variant, multi_pod)
    shape = INPUT_SHAPES[shape_name]
    ok, why = supported(cfg, shape)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "variant": variant or "baseline",
           "status": "skipped" if not ok else "?", "skip_reason": why}
    if not ok:
        print(f"[dryrun] SKIP {arch} × {shape_name}: {why}")
        return rec

    if variant.startswith("serve_mesh"):
        # serving-specific mesh: model axis sized to divide the kv heads so
        # the decode cache shards cleanly (same 256 chips, different shape)
        mesh = mesh_lib.make_mesh((32, 8), ("data", "model"))
    else:
        mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    with mesh:
        full = _compile_and_measure(cfg, shape, mesh, variant=variant)
    chips = mesh.devices.size
    mem = full["mem"]

    def _mem_field(name):
        v = getattr(mem, name, None)
        return int(v) if v is not None else None

    rec.update({
        "status": "ok",
        "chips": chips,
        "meta": full["bundle"].meta,
        "lower_s": round(full["lower_s"], 2),
        "compile_s": round(full["compile_s"], 2),
        "hlo_flops_scanbody_once": full["flops"],
        "hlo_bytes_scanbody_once": full["bytes_accessed"],
        "collective_bytes_scanbody_once": full["coll"],
        "model_flops": model_flops(cfg, shape),
        "memory": {
            "argument_bytes": _mem_field("argument_size_in_bytes"),
            "output_bytes": _mem_field("output_size_in_bytes"),
            "temp_bytes": _mem_field("temp_size_in_bytes"),
            "generated_code_bytes": _mem_field("generated_code_size_in_bytes"),
            "alias_bytes": _mem_field("alias_size_in_bytes"),
        },
    })
    print(f"[dryrun] OK {arch} × {shape_name} × {rec['mesh']} "
          f"(lower {full['lower_s']:.1f}s, compile {full['compile_s']:.1f}s)")
    print(f"  memory_analysis: {mem}")

    # --- probe-corrected totals (single-pod roofline only) -----------------
    if probes and not multi_pod:
        pcfgs = rf.probe_configs(cfg)
        pmetrics = []
        for pc in pcfgs:
            with mesh:
                pm = _compile_and_measure(pc, shape, mesh, variant=variant)
            entry = {"flops": pm["flops"], "bytes": pm["bytes_accessed"]}
            for k in ("all-gather", "all-reduce", "reduce-scatter",
                      "all-to-all", "collective-permute", "total"):
                entry[f"coll_{k}"] = float(pm["coll"][k])
            pmetrics.append(entry)
        pred = rf.extrapolate(cfg, pcfgs, pmetrics)
        rec["hlo_flops"] = pred["flops"]
        rec["hlo_bytes_accessed"] = pred["bytes"]
        rec["collective_bytes"] = {
            k.replace("coll_", ""): v for k, v in pred.items()
            if k.startswith("coll_")}
        rec["probe_layers"] = [c.num_layers for c in pcfgs]
        rec["roofline"] = rf.roofline_terms(
            pred["flops"], pred["bytes"], pred["coll_total"])
        rec["useful_flops_ratio"] = (
            (rec["model_flops"] / chips) / max(1.0, pred["flops"]))
        print(f"  corrected: flops={pred['flops']:.3e}/chip "
              f"bytes={pred['bytes']:.3e}/chip coll={pred['coll_total']:.3e}B/chip")
        print(f"  roofline: {rec['roofline']} "
              f"useful_ratio={rec['useful_flops_ratio']:.3f}")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--variant", default="")
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args()

    archs = ASSIGNED_ARCHS if (args.all or not args.arch) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multipod]

    os.makedirs(args.out, exist_ok=True)
    results = []
    for arch in archs:
        for shape_name in shapes:
            for mp in meshes:
                tag = f"{arch}__{shape_name}__{'2x16x16' if mp else '16x16'}"
                if args.variant:
                    tag += f"__{args.variant}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path):
                    print(f"[dryrun] cached {tag}")
                    results.append(json.load(open(path)))
                    continue
                try:
                    rec = run_one(arch, shape_name, mp, variant=args.variant)
                except Exception as e:  # noqa: BLE001 — record and continue
                    rec = {"arch": arch, "shape": shape_name,
                           "mesh": "2x16x16" if mp else "16x16",
                           "variant": args.variant or "baseline",
                           "status": "error", "error": repr(e),
                           "trace": traceback.format_exc()[-2000:]}
                    print(f"[dryrun] ERROR {tag}: {e!r}")
                with open(path, "w") as f:
                    json.dump(rec, f, indent=2)
                results.append(rec)

    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"[dryrun] done: {n_ok} ok, {n_skip} skipped, {n_err} errors "
          f"of {len(results)}")
    return 0 if n_err == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
