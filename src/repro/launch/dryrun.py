import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))
# ^ MUST precede any jax import: jax locks the device count at first init.
# (This also means: no `from __future__ import annotations` in this module.)

"""Multi-pod dry-run driver (assignment deliverable (e)).


For every (architecture × input shape × mesh) cell:
    lowered  = jax.jit(step_fn).lower(*input_specs(...))
    compiled = lowered.compile()
    record memory_analysis() + cost_analysis() + collective bytes

Meshes: single-pod 16×16 ("data","model") and two-pod 2×16×16
("pod","data","model").  Kinds per shape: train_4k -> train_step,
prefill_32k -> prefill, decode_32k / long_500k -> serve (decode) step.

Results are cached as JSON under --out so the full sweep is resumable;
`--all` iterates cells in-process, the Makefile-style sweep in
benchmarks/run_dryruns.sh uses one subprocess per cell for isolation.
"""

import argparse
import json
import re
import sys
import time
import traceback
from typing import Dict, Optional

import jax

from ..configs import base as cfgbase
from ..launch import sharding as shlib
from ..launch import specs as specs_mod
from ..launch.mesh import make_production_mesh
from ..models import transformer as model
from . import hlo_cost
from ..train.optimizer import OptHyper
from ..train.step import make_train_step

COLLECTIVE_RE = re.compile(
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"[^=]*=\s*(\([^)]*\)|\S+)\s")
SHAPE_RE = re.compile(r"(bf16|f16|f32|f64|s8|u8|s16|u16|s32|u32|s64|u64|pred)"
                      r"\[([0-9,]*)\]")
BYTES_OF = {"bf16": 2, "f16": 2, "f32": 4, "f64": 8, "s8": 1, "u8": 1,
            "pred": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4, "s64": 8,
            "u64": 8}


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Sum output-operand bytes of every collective op in the (post-SPMD)
    HLO.  Per-device numbers, like cost_analysis."""
    out: Dict[str, float] = {}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = re.match(r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|[^\s(]+)\s+"
                     r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
                     r"collective-permute)", stripped)
        if not m:
            continue
        shapes_blob, op = m.group(1), m.group(2)
        nbytes = 0
        for dt, dims in SHAPE_RE.findall(shapes_blob):
            n = 1
            if dims:
                for d in dims.split(","):
                    n *= int(d)
            nbytes += n * BYTES_OF[dt]
        out[op] = out.get(op, 0.0) + float(nbytes)
    return out


def step_fn_for(cfg, kind: str, *, attn_chunk: int = 1024,
                skip_upper_triangle: bool = True):
    if kind == "train":
        return make_train_step(cfg, OptHyper(), attn_chunk=attn_chunk,
                               skip_upper_triangle=skip_upper_triangle)
    if kind == "prefill":
        def prefill_step(params, batch):
            max_seq = batch["tokens"].shape[1] + (cfg.n_patches or 0)
            return model.prefill(params, cfg, batch, max_seq=max_seq,
                                 chunk=attn_chunk)
        return prefill_step
    if kind == "decode":
        if cfg.is_encoder_decoder:
            def serve_step(params, cache, tokens, pos, enc_out):
                return model.decode_step(params, cfg, cache, tokens, pos,
                                         enc_out=enc_out)
        else:
            def serve_step(params, cache, tokens, pos):
                return model.decode_step(params, cfg, cache, tokens, pos)
        return serve_step
    raise ValueError(kind)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             attn_chunk: int = 1024, skip_upper_triangle: bool = True,
             want_hlo: bool = False, moe_impl: str = None,
             overrides: Dict = None) -> Dict:
    import dataclasses
    cfg = cfgbase.get_config(arch)
    if moe_impl:
        cfg = dataclasses.replace(cfg, moe_impl=moe_impl)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    if arch == "ringo-graph":
        from .ringo_cells import run_ringo_cell
        return run_ringo_cell(shape_name, multi_pod)
    shape = cfgbase.runnable_shapes(cfg).get(shape_name)
    if shape is None:
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "status": "skipped",
                "reason": "long_500k needs sub-quadratic attention "
                          "(DESIGN.md §Arch-applicability)"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    kind = shape.kind
    t0 = time.time()
    rules, args = specs_mod.input_specs(cfg, shape, mesh, kind)
    fn = step_fn_for(cfg, kind, attn_chunk=attn_chunk,
                     skip_upper_triangle=skip_upper_triangle)
    with mesh, shlib.rules_ctx(rules):
        lowered = jax.jit(fn).lower(*args)
        compiled = lowered.compile()
    t1 = time.time()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    # scan-corrected cost model (while bodies × trip counts) — see hlo_cost
    corrected = hlo_cost.analyze_hlo(hlo)
    n_chips = mesh.devices.size
    result = {
        "arch": arch, "shape": shape_name, "kind": kind,
        "multi_pod": multi_pod, "status": "ok",
        "n_chips": int(n_chips),
        "compile_s": round(t1 - t0, 1),
        # raw XLA numbers (while bodies counted once — understated)
        "xla_flops_per_device": float(cost.get("flops", 0.0)),
        "xla_bytes_per_device": float(cost.get("bytes accessed", 0.0)),
        # trip-count-corrected numbers (used by §Roofline)
        "flops_per_device": corrected.flops,
        "bytes_per_device": corrected.bytes,
        "collective_bytes_per_device": corrected.collective_bytes,
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", 0) or
            (getattr(mem, "argument_size_in_bytes", 0)
             + getattr(mem, "temp_size_in_bytes", 0)),
        },
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
        "options": {"attn_chunk": attn_chunk,
                    "skip_upper_triangle": skip_upper_triangle},
    }
    if want_hlo:
        result["hlo"] = hlo
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--out", default="benchmarks/dryrun_results")
    ap.add_argument("--attn-chunk", type=int, default=1024)
    ap.add_argument("--no-triangle-skip", action="store_true",
                    help="baseline attention: full rectangular chunk loop")
    ap.add_argument("--moe-impl", default=None,
                    choices=[None, "sorted", "expert_tp"])
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args(argv)

    archs = ([args.arch] if args.arch else
             [a for a in cfgbase.list_archs() if a != "ringo-graph"])
    shapes = [args.shape] if args.shape else list(cfgbase.SHAPES)
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    if args.list:
        for a in archs:
            cfg = cfgbase.get_config(a)
            runnable = list(cfgbase.runnable_shapes(cfg)) \
                if a != "ringo-graph" else ["pagerank_twitter",
                                            "pagerank_livejournal"]
            skipped = [s for s in cfgbase.SHAPES if s not in runnable]
            print(f"{a:26s} runs={runnable} skips={skipped}")
        return 0

    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                mesh_name = "multi" if mp else "single"
                fname = os.path.join(
                    args.out,
                    f"{args.tag}.{arch}.{shape}.{mesh_name}.json")
                if os.path.exists(fname) and not args.force:
                    print(f"[dryrun] cached {fname}")
                    continue
                try:
                    res = run_cell(arch, shape, mp,
                                   attn_chunk=args.attn_chunk,
                                   skip_upper_triangle=not args.no_triangle_skip,
                                   moe_impl=args.moe_impl)
                except Exception as e:  # record failures, keep sweeping
                    res = {"arch": arch, "shape": shape, "multi_pod": mp,
                           "status": "error", "error": repr(e),
                           "traceback": traceback.format_exc()}
                    failures += 1
                with open(fname, "w") as f:
                    json.dump(res, f, indent=1)
                status = res["status"]
                extra = ""
                if status == "ok":
                    extra = (f" flops/dev={res['flops_per_device']:.3e}"
                             f" peak={res['memory']['peak_bytes']/2**30:.2f}GiB"
                             f" compile={res['compile_s']}s")
                print(f"[dryrun] {arch} × {shape} × {mesh_name}: {status}{extra}")
                if status == "error":
                    print(res["error"])
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
