import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production mesh, extract memory/cost/collective roofline terms.

MUST be run as its own process (the XLA_FLAGS line above executes before any
jax import, and jax locks device count on first init):

    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh multi

Results are appended as JSON files under experiments/dryrun/ (skip-if-exists,
so the sweep is resumable).
"""
import argparse
import json
import re
import sys
import time
import traceback
from pathlib import Path

import jax

from repro.configs import ASSIGNED, get_config
from repro.launch.hlo_analysis import COLLECTIVES, analyze
from repro.launch.mesh import make_production_mesh
from repro.launch.sharding import spec_summary
from repro.launch.specs import SHAPES, input_specs, shape_applicable
from repro.models import model as M

# ---- hardware constants (TPU v5e) ----------------------------------------
PEAK_FLOPS = 197e12          # bf16 per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4,
    "u16": 2, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"(\w+)\[([\d,]*)\][^=]*?\s(all-gather|all-reduce|reduce-scatter|"
    r"all-to-all|collective-permute)")
_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|f8\w*|s64|s32|s16|s8|u64|u32|u16|u8|pred)\[([\d,]*)\]")


def collective_bytes(hlo_text: str) -> dict:
    """Sum output bytes of every collective op in the (SPMD-partitioned) HLO."""
    out = {"all-gather": 0, "all-reduce": 0, "reduce-scatter": 0,
           "all-to-all": 0, "collective-permute": 0, "count": 0}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        # e.g.:  %ag = bf16[8,128]{1,0} all-gather(...)  or tuple variants
        m = re.search(r"\s(all-gather|all-reduce|reduce-scatter|all-to-all|"
                      r"collective-permute)(-start|-done)?\(", stripped)
        if not m or "-done(" in stripped:
            continue
        kind = m.group(1)
        lhs = stripped.split(" = ", 1)
        if len(lhs) != 2:
            continue
        shapes = _SHAPE_RE.findall(lhs[1].split(kind)[0])
        nbytes = 0
        for dt, dims in shapes:
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            key = "f8" if dt.startswith("f8") else dt
            nbytes += n * _DTYPE_BYTES.get(key, 4)
        out[kind] += nbytes
        out["count"] += 1
    return out


def roofline(totals, raw_cost: dict, n_chips: int, cfg, shape_name: str) -> dict:
    """Three-term roofline from the trip-count-aware HLO analysis.

    NOTE: raw ``cost_analysis()`` visits while bodies once and is therefore
    useless for scanned programs; ``totals`` comes from
    ``hlo_analysis.analyze`` which multiplies through known_trip_counts.
    flops = matmul (dot) flops; bytes = dot operand+output traffic (HBM
    upper bound ignoring fusion reuse); both per-chip (post-SPMD program).
    """
    flops = totals.flops
    nbytes = totals.dot_bytes
    coll_b = sum(totals.coll[k] for k in COLLECTIVES)
    t_compute = flops / PEAK_FLOPS
    t_memory = nbytes / HBM_BW
    t_coll = coll_b / ICI_BW
    dominant = max((t_compute, "compute"), (t_memory, "memory"),
                   (t_coll, "collective"))[1]
    info = SHAPES[shape_name]
    tokens = info["batch"] * (info["seq"] if info["kind"] != "decode" else 1)
    model_flops = 6.0 * cfg.n_active_params() * tokens if info["kind"] == "train" \
        else 2.0 * cfg.n_active_params() * tokens
    total_flops = flops * n_chips
    return {
        "hlo_flops_per_chip": flops,
        "hlo_dot_bytes_per_chip": nbytes,
        "collective_bytes_per_chip": coll_b,
        "raw_cost_analysis_flops": float(raw_cost.get("flops", 0.0)),
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_coll,
        "dominant": dominant,
        "model_flops": model_flops,
        "useful_ratio": model_flops / total_flops if total_flops else 0.0,
        "collectives": dict(totals.coll, count=totals.coll_count),
    }


def apply_opts(cfg, opts):
    """§Perf optimization toggles (see EXPERIMENTS.md §Perf)."""
    import dataclasses
    from repro.launch import sharding as SH
    from repro.models import layers as L
    if "moe-capacity" in opts:
        cfg = dataclasses.replace(cfg, moe_impl="capacity")
    if "attn-fallback" in opts:
        SH.ATTN_REPLICATE_IF_RAGGED = True
    if "seq-par" in opts:
        L.SEQ_PARALLEL_AXIS = "model"
    if "flat-gqa" in opts:
        L.FLAT_GQA = True
    return cfg


def run_one(arch: str, shape_name: str, multi_pod: bool, out_dir: Path,
            force: bool = False, opts=()) -> dict:
    mesh_name = "multi" if multi_pod else "single"
    tag = ("__" + "+".join(sorted(opts))) if opts else ""
    out_file = out_dir / f"{arch}__{shape_name}__{mesh_name}{tag}.json"
    if out_file.exists() and not force:
        return json.loads(out_file.read_text())
    cfg = apply_opts(get_config(arch), opts)
    ok, reason = shape_applicable(cfg, shape_name)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "opts": sorted(opts)}
    if not ok:
        rec.update(status="skipped", reason=reason)
        out_file.write_text(json.dumps(rec, indent=2))
        return rec
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        n_chips = mesh.devices.size
        # surface the CHOSEN partition specs (incl. silent replication
        # fallbacks for ragged head counts) next to the roofline numbers
        params_shape = jax.eval_shape(
            lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
        spec_text = spec_summary(cfg, mesh, params_shape)
        print(spec_text, flush=True)
        rec["partition_specs"] = spec_text.splitlines()
        with mesh:
            fn, args, donate, out_sh = input_specs(cfg, shape_name, mesh)
            jitted = jax.jit(fn, donate_argnums=donate, out_shardings=out_sh)
            lowered = jitted.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            totals = analyze(compiled.as_text())
        rl = roofline(totals, cost or {}, n_chips, cfg, shape_name)
        rec.update(
            status="ok",
            n_chips=int(n_chips),
            lower_s=round(t_lower, 1),
            compile_s=round(t_compile, 1),
            memory={
                "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                "output_bytes": getattr(mem, "output_size_in_bytes", None),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
            },
            roofline=rl,
        )
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
    out_file.write_text(json.dumps(rec, indent=2))
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--opt", action="append", default=[])
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    archs = ASSIGNED if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.mesh == "both" else [args.mesh == "multi"]

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                rec = run_one(arch, shape, mp, out_dir, force=args.force,
                              opts=tuple(args.opt))
                status = rec["status"]
                extra = ""
                if status == "ok":
                    rl = rec["roofline"]
                    extra = (f"dom={rl['dominant']} "
                             f"tc={rl['t_compute_s']:.3e} "
                             f"tm={rl['t_memory_s']:.3e} "
                             f"tx={rl['t_collective_s']:.3e} "
                             f"peak={_gb(rec['memory']['peak_bytes'])}")
                elif status == "error":
                    failures += 1
                    extra = rec["error"][:120]
                else:
                    extra = rec.get("reason", "")[:60]
                print(f"[{status:7s}] {arch:24s} {shape:12s} "
                      f"{'multi' if mp else 'single':6s} {extra}", flush=True)
    sys.exit(1 if failures else 0)


def _gb(x):
    return f"{x / 2**30:.2f}GiB" if isinstance(x, (int, float)) and x else "?"


if __name__ == "__main__":
    main()
