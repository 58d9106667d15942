import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (architecture × input-shape)
cell on the production meshes, and dump the roofline inputs.

The two lines above MUST stay the first statements in this module: jax
locks the device count on first initialization, and the dry-run needs
512 placeholder CPU devices to build the 16×16 and 2×16×16 meshes.
(Only the dry-run: smoke tests and benches see the 1 real device.)

Per cell this produces artifacts/dryrun/<arch>.<shape>.<mesh>.json with:
  * compiled.cost_analysis() FLOPs / bytes accessed,
  * compiled.memory_analysis() per-device byte breakdown,
  * collective bytes by op kind, parsed from the optimized HLO,
  * MODEL_FLOPS (6·N·D train / 2·N·D forward, N_active for MoE).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                    # all cells
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-405b \
      --shape train_4k --mesh single
"""
import argparse
import json
import re
import time
import traceback
from pathlib import Path

import jax

from repro.configs import ARCHS, SHAPES, get_config, shape_applicable
from repro.launch import specs as specs_mod
from repro.launch.mesh import make_production_mesh
from repro.launch.serve import make_prefill_step, make_serve_step
from repro.launch.train import (AdamWConfig, TrainPlan, abstract_state,
                                default_plan, make_train_step)

# ---------------------------------------------------------------------------
# HLO collective parsing
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


_BUF_RE = re.compile(r"= (f32|bf16)\[([\d,]+)\]")


def cpu_bf16_inflation(hlo_text: str) -> int:
    """Estimate bytes of f32 buffers that exist only because the CPU
    backend legalizes bf16 by converting to f32 (convert fusions create
    an f32 twin of each large bf16 tensor).  On a real TPU these twins
    don't exist; the dry-run subtracts them to report a TPU-adjusted
    temp figure.  Heuristic: an f32 buffer whose dims exactly match a
    bf16 buffer in the same module is counted as legalization."""
    bf16_shapes: set[str] = set()
    f32: dict[str, int] = {}
    for m in _BUF_RE.finditer(hlo_text):
        dt, dims = m.group(1), m.group(2)
        if dt == "bf16":
            bf16_shapes.add(dims)
        else:
            n = 1
            for d in dims.split(","):
                n *= int(d)
            f32[dims] = n * 4
    return sum(v for dims, v in f32.items()
               if dims in bf16_shapes and v > 1 << 26)


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum result-shape bytes of every collective op in optimized HLO."""
    out = {k: 0 for k in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        s = line.strip()
        # '%name = TYPE op-name(' — find which collective, if any
        for kind in _COLLECTIVES:
            token = f" {kind}("
            alt = f" {kind}-start("
            if token in s or alt in s:
                eq = s.find("= ")
                if eq < 0:
                    continue
                paren = s.find(token if token in s else alt)
                type_str = s[eq + 2:paren]
                out[kind] += _shape_bytes(type_str)
                out["count"] += 1
                break
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


# ---------------------------------------------------------------------------
# Model-FLOPs reference
# ---------------------------------------------------------------------------


def recurrent_correction(cfg, shape, data_shards: int = 16) -> float:
    """Analytic per-device FLOPs of the recurrent chunk scans that XLA's
    cost analysis counts only once (the chunk loop stays rolled even in
    the cost pass — flattening it is compile-prohibitive; measured).

    Covers the mamba branch (hybrid) and mLSTM/sLSTM blocks (ssm).
    Forward-only analytic count × 4 for training (bwd 2×, remat re-fwd
    1×) — the same overhead the measured cells show.  Exact chunk math
    mirrors chunked_linear_attention's einsums.
    """
    if cfg.family not in ("hybrid", "ssm") or shape.kind == "decode":
        return 0.0
    from repro.models.ssm import SSM_HEAD_DIM, mamba_dims, mlstm_dims
    b, t = shape.global_batch, shape.seq_len
    c = 128
    nc = max(t // c, 1)

    def chunk_flops(h, dk, dv):
        per_chunk = b * h * (2 * c * c * (dk + dv) + 4 * c * dk * dv
                             + 2 * c * c)
        return (nc - 1) * per_chunk          # one chunk already counted

    total = 0.0
    if cfg.family == "hybrid":
        _, nh, ds = mamba_dims(cfg)
        total += cfg.n_layers * chunk_flops(nh, ds, SSM_HEAD_DIM)
    else:                                    # xlstm
        _, nh, dh = mlstm_dims(cfg)
        n_mlstm = sum(n * seg.repeat for seg in cfg.plan()
                      for sp, n in seg.pattern if sp.kind == "mlstm")
        n_slstm = sum(n * seg.repeat for seg in cfg.plan()
                      for sp, n in seg.pattern if sp.kind == "slstm")
        total += n_mlstm * chunk_flops(nh, dh, dh + 1)
        d_inner = cfg.ssm_expand * cfg.d_model
        total += n_slstm * (t - 1) * 20 * b * d_inner   # elementwise scan
    if shape.kind == "train":
        total *= 4.0
    shards = data_shards if b % data_shards == 0 else 1
    return total / shards


def model_flops(cfg, shape) -> float:
    """6·N·D (train) / 2·N·D (forward) with N_active for MoE."""
    from repro.sim.costmodel import CostModel
    n = CostModel(cfg).n_active_params()
    if shape.kind == "train":
        toks = shape.global_batch * shape.seq_len
        return 6.0 * n * toks
    if shape.kind == "prefill":
        toks = shape.global_batch * shape.seq_len
        return 2.0 * n * toks
    return 2.0 * n * shape.global_batch          # one decode token / seq


# ---------------------------------------------------------------------------
# One cell
# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# Layer-scaled cost extrapolation
# ---------------------------------------------------------------------------
#
# The unrolled cost pass is exact but unrolling 126 layers at a 256-way
# mesh does not compile in reasonable time on this 1-core container.
# Per-layer cost is structurally linear in the number of repeating units
# (identical blocks, identical sharding), so for deep/wide archs we
# compile the unrolled module at TWO reduced depths and extrapolate:
#
#     F(u) = outer + u * per_unit      (u = number of repeating units)
#
# outer (embed/unembed/loss/optimizer/batch reshards) and per_unit
# (block compute + its FSDP gathers / TP reduces) both live at the full
# production mesh, so sharding effects are captured exactly.  Exact for
# homogeneous stacks; gemma3's trailing partial period (2 local layers
# of a 6-layer pattern) is approximated by a fractional unit (<2% of
# depth).  Records carry "cost_mode": "direct" | "extrapolated".

_DIRECT_MAX_LAYERS = 48          # unroll directly when depth*width is small
_DIRECT_MAX_DMODEL = 4096


def _period(cfg) -> int:
    if cfg.local_global_ratio > 0:
        return cfg.local_global_ratio + 1
    if cfg.mlstm_ratio > 0:
        return cfg.mlstm_ratio + 1
    return 1


def _scaled_cfg(cfg, units: int):
    period = _period(cfg)
    n = cfg.first_k_dense + units * period
    kw = {"n_layers": n, "scan_layers": False, "loss_chunk": 0}
    if cfg.global_layers:
        density = len(cfg.global_layers) / cfg.n_layers
        k = max(1, round(density * n))
        kw["global_layers"] = tuple(min(n - 1, int(i * n / k) + 1)
                                    for i in range(k))
    if cfg.enc_layers:
        kw["enc_layers"] = n
    return cfg.replace(**kw)


def _units_full(cfg) -> float:
    return (cfg.n_layers - cfg.first_k_dense) / _period(cfg)


def _direct_ok(cfg) -> bool:
    if cfg.family in ("hybrid", "ssm"):
        # recurrent branches unroll their chunk scans in cost mode —
        # direct full-depth unrolls are compile-prohibitive; extrapolate
        return False
    return (cfg.n_layers <= _DIRECT_MAX_LAYERS
            and cfg.d_model <= _DIRECT_MAX_DMODEL)


def _costs_of(compiled) -> dict:
    cost = compiled.cost_analysis() or {}
    coll = collective_bytes(compiled.as_text())
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
            "collectives": coll}


def _lin(fa: dict, fb: dict, ua: float, ub: float, u: float) -> dict:
    def go(a, b):
        if isinstance(a, dict):
            return {k: go(a[k], b[k]) for k in a}
        slope = (b - a) / (ub - ua)
        return max(0.0, a + slope * (u - ua))
    out = go(fa, fb)
    out["collectives"] = {k: (int(v) if k == "count" else v)
                          for k, v in out["collectives"].items()}
    return out


def _compile_cell(cfg, shape, mesh, plan: TrainPlan | None):
    """Lower + compile one step for this cell; returns (compiled, plan)."""
    chips = mesh.devices.size
    ins = specs_mod.input_specs(cfg, shape)
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            plan = plan or default_plan(cfg, shape, chips)
            acfg = AdamWConfig(int8_moments=plan.int8_moments)
            step, _ = make_train_step(cfg, mesh, plan, acfg, shape=shape)
            p, o = abstract_state(cfg, acfg)
            lowered = step.lower(p, o, ins["batch"])
        elif shape.kind == "prefill":
            step, _ = make_prefill_step(cfg, mesh, shape)
            p, _ = abstract_state(cfg, AdamWConfig())
            args = [p, ins["tokens"], ins["cache"]]
            if cfg.frontend == "patch":
                args.append(ins["vision_embeds"])
            elif cfg.is_encdec:
                args.append(ins["frames"])
            lowered = step.lower(*args)
        else:
            step, _ = make_serve_step(cfg, mesh, shape)
            p, _ = abstract_state(cfg, AdamWConfig())
            lowered = step.lower(p, ins["tokens"], ins["cache"])
        return lowered.compile(), plan


def lower_cell(arch: str, shape_name: str, mesh, mesh_name: str,
               plan: TrainPlan | None = None,
               cost_pass: bool = True) -> dict:
    """Two lowerings per cell:

    * **memory pass** — production form (lax.scan over layers + remat +
      the real microbatch plan): proves the sharding compiles and gives
      the deployable per-device memory picture.
    * **cost pass** (single-pod roofline cells only) — unrolled layers,
      microbatch=1: XLA's cost_analysis counts while-loop bodies once,
      so only the unrolled module yields honest FLOP/byte/collective
      totals.  Numerically identical modulo bf16 reassociation (tested).
    """
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "skipped": why}

    t0 = time.time()
    compiled, plan = _compile_cell(cfg, shape, mesh, plan)
    t_mem = time.time() - t0
    mem = compiled.memory_analysis()
    inflation = cpu_bf16_inflation(compiled.as_text())
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "chips": mesh.devices.size,
        "model_flops": model_flops(cfg, shape),
        "compile_s": round(t_mem, 1),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", 0),
            # conservative resident bound: inputs (donated outputs alias
            # them) + live temporaries
            "peak_bytes": (getattr(mem, "argument_size_in_bytes", 0)
                           + getattr(mem, "temp_size_in_bytes", 0)),
            "xla_peak_bytes": getattr(mem, "peak_memory_in_bytes", 0),
            # CPU-backend bf16 legalization creates f32 twins of large
            # bf16 buffers; a TPU build doesn't have them
            "cpu_bf16_inflation_bytes": inflation,
            "tpu_adjusted_peak_bytes": max(
                0, getattr(mem, "argument_size_in_bytes", 0)
                + getattr(mem, "temp_size_in_bytes", 0) - inflation),
        },
    }
    if plan is not None and shape.kind == "train":
        rec["plan"] = {"microbatch": plan.microbatch,
                       "accum_dtype": plan.accum_dtype,
                       "int8_moments": plan.int8_moments}

    if cost_pass:
        t1 = time.time()
        plan_u = TrainPlan(microbatch=1,
                           int8_moments=(plan.int8_moments
                                         if plan else False)) \
            if shape.kind == "train" else None
        # cost configs: unrolled layers AND unchunked loss — every scan
        # body must be gone or its flops are undercounted
        if _direct_ok(cfg):
            cfg_u = cfg.replace(scan_layers=False, loss_chunk=0)
            compiled_u, _ = _compile_cell(cfg_u, shape, mesh, plan_u)
            rec.update(_costs_of(compiled_u))
            rec["cost_mode"] = "direct"
        else:
            per = _period(cfg)
            if per >= 6:
                ua, ub = 1, 2              # one/two full patterns
            elif cfg.global_layers:
                ua, ub = 8, 16             # keep the global-layer density
            else:
                ua, ub = 2, 4
            ca, _ = _compile_cell(_scaled_cfg(cfg, ua), shape, mesh, plan_u)
            fa = _costs_of(ca)
            del ca
            cb, _ = _compile_cell(_scaled_cfg(cfg, ub), shape, mesh, plan_u)
            fb = _costs_of(cb)
            del cb
            rec.update(_lin(fa, fb, ua, ub, _units_full(cfg)))
            rec["cost_mode"] = f"extrapolated(u={ua},{ub})"
        corr = recurrent_correction(cfg, shape)
        if corr > 0:
            rec["recurrent_correction_flops"] = corr
            rec["flops"] = rec.get("flops", 0.0) + corr
        rec["cost_compile_s"] = round(time.time() - t1, 1)
    return rec


def run(archs, shapes, meshes, out_dir: Path,
        stop_on_error: bool = False) -> list[dict]:
    out_dir.mkdir(parents=True, exist_ok=True)
    results = []
    mesh_objs = {}
    if "single" in meshes:
        mesh_objs["single"] = make_production_mesh(multi_pod=False)
    if "multi" in meshes:
        mesh_objs["multi"] = make_production_mesh(multi_pod=True)

    for arch in archs:
        for shape_name in shapes:
            for mesh_name, mesh in mesh_objs.items():
                tag = f"{arch}.{shape_name}.{mesh_name}"
                try:
                    # cost pass (unrolled) only for the single-pod
                    # roofline cells; multi-pod is the sharding proof
                    rec = lower_cell(arch, shape_name, mesh, mesh_name,
                                     cost_pass=(mesh_name == "single"))
                except Exception as e:
                    if stop_on_error:
                        raise
                    rec = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_name, "error": repr(e),
                           "trace": traceback.format_exc()[-2000:]}
                results.append(rec)
                (out_dir / f"{tag}.json").write_text(
                    json.dumps(rec, indent=1))
                if "skipped" in rec:
                    print(f"SKIP {tag}: {rec['skipped']}", flush=True)
                elif "error" in rec:
                    print(f"FAIL {tag}: {rec['error']}", flush=True)
                else:
                    peak = rec["memory"]["peak_bytes"] / 1e9
                    extra = ""
                    if "flops" in rec:
                        extra = (f"{rec['flops']:.3e} FLOPs "
                                 f"{rec['bytes_accessed']:.3e} B "
                                 f"coll={rec['collectives']['total']:.3e} B ")
                    print(f"OK   {tag}: {extra}peak={peak:.2f} GB/dev "
                          f"compile={rec['compile_s']}"
                          f"+{rec.get('cost_compile_s', 0)}s", flush=True)
    return results


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--stop-on-error", action="store_true")
    args = ap.parse_args()

    archs = list(ARCHS) if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    results = run(archs, shapes, meshes, Path(args.out),
                  stop_on_error=args.stop_on_error)
    failed = [r for r in results if "error" in r]
    print(f"\n{len(results)} cells: {len(failed)} failed, "
          f"{sum(1 for r in results if 'skipped' in r)} skipped")
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
