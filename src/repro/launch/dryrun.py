"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh)
combination on placeholder devices, extract memory/cost/collective analysis,
and derive the three-term roofline.

MUST be the first import in the process: the XLA_FLAGS below forces 512 host
devices and jax locks the device count at first init. (Do not import this
module from tests/benchmarks — they should see 1 device.)

Scan-correction methodology (EXPERIMENTS.md §Dry-run): XLA's cost_analysis
counts a `while` (scan) body once, so per-layer costs are reconstructed by
compiling small *unrolled* probe configs (1 and 2 pattern groups + tail) and
differencing — all numbers still come from compiled artifacts:

    group  = f(2P) - f(P)          base = f(P) - group
    total  = base + reps*group (+ tail from a third probe)

Collective bytes are parsed from the compiled HLO (operand bytes of
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute)
and extrapolated identically.
"""
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")

import argparse        # noqa: E402
import dataclasses     # noqa: E402
import json            # noqa: E402
import re              # noqa: E402
import time            # noqa: E402
from dataclasses import replace  # noqa: E402

import numpy as np     # noqa: E402
import jax             # noqa: E402

from ..models import registry  # noqa: E402
from ..models.config import INPUT_SHAPES, InputShape, ModelConfig  # noqa: E402
from . import inputs as I  # noqa: E402
from .mesh import make_production_mesh, rules_for_config  # noqa: E402
from .steps import BUILDERS  # noqa: E402

# TPU v5e hardware model (per chip)
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s/link
HBM_CAP = 16 * 2**30

COLLECTIVE_RE = re.compile(
    r"(\w[\w.\-]*)\s*=\s*((?:bf16|f16|f32|f64|s8|u8|s16|s32|u32|s64|i32|pred)"
    r"\[[\d,]*\][^ ]*|\([^)]*\))\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)")

DTYPE_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "f64": 8, "s8": 1, "u8": 1,
               "s16": 2, "s32": 4, "u32": 4, "s64": 8, "i32": 4, "pred": 1}


def collective_bytes(hlo_text: str) -> dict:
    """Sum output-shape bytes per collective kind from HLO text."""
    out = {}
    for m in COLLECTIVE_RE.finditer(hlo_text):
        shape_s, kind = m.group(2), m.group(3)
        total = 0
        for dt, dims in re.findall(r"(bf16|f16|f32|f64|s8|u8|s16|s32|u32|s64|i32|pred)\[([\d,]*)\]",
                                   shape_s):
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * DTYPE_BYTES[dt]
        out[kind] = out.get(kind, 0) + total
    return out


# ---------------------------------------------------------------------------
# Compile + analyze
# ---------------------------------------------------------------------------

def compile_and_analyze(cfg, shape, mesh, rules, want_hlo=True):
    fn, args, shardings, donate = BUILDERS[shape.mode](cfg, shape, mesh, rules)
    t0 = time.perf_counter()
    jitted = jax.jit(fn, in_shardings=shardings, donate_argnums=donate)
    lowered = jitted.lower(*args)
    t_lower = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0
    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    coll = collective_bytes(compiled.as_text()) if want_hlo else {}
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "collective_bytes": coll,
        "mem_args": int(ma.argument_size_in_bytes),
        "mem_out": int(ma.output_size_in_bytes),
        "mem_temp": int(ma.temp_size_in_bytes),
        "mem_alias": int(ma.alias_size_in_bytes),
        "t_lower_s": round(t_lower, 2),
        "t_compile_s": round(t_compile, 2),
    }


def probe_cfg(cfg: ModelConfig, n_layers: int, enc_scale: float = None):
    upd = dict(n_layers=n_layers, scan_layers=False)
    if cfg.encoder_layers:
        upd["encoder_layers"] = n_layers
    return replace(cfg, **upd)


def extrapolated_costs(cfg, shape, mesh, rules):
    """Per-layer reconstruction via unrolled probe compiles (see module doc)."""
    Pn = len(cfg.pattern)
    reps, tail = cfg.n_layers // Pn, cfg.n_layers % Pn
    f1 = compile_and_analyze(probe_cfg(cfg, Pn), shape, mesh, rules)
    f2 = compile_and_analyze(probe_cfg(cfg, 2 * Pn), shape, mesh, rules)

    def combine(key, is_dict=False):
        if is_dict:
            keys = set(f1[key]) | set(f2[key])
            group = {k: f2[key].get(k, 0) - f1[key].get(k, 0) for k in keys}
            base = {k: f1[key].get(k, 0) - group.get(k, 0) for k in keys}
            total = {k: base[k] + reps * group[k] for k in keys}
            return total, group
        group = f2[key] - f1[key]
        base = f1[key] - group
        return base + reps * group, group

    flops, flops_group = combine("flops")
    byts, _ = combine("bytes_accessed")
    coll, coll_group = combine("collective_bytes", is_dict=True)
    if tail:
        f3 = compile_and_analyze(probe_cfg(cfg, 2 * Pn + tail), shape, mesh,
                                 rules)
        flops += f3["flops"] - f2["flops"]
        byts += f3["bytes_accessed"] - f2["bytes_accessed"]
        for k in coll:
            coll[k] = coll.get(k, 0) + f3["collective_bytes"].get(k, 0) \
                - f2["collective_bytes"].get(k, 0)
    return {"flops": max(flops, 0.0), "bytes_accessed": max(byts, 0.0),
            "collective_bytes": {k: max(v, 0) for k, v in coll.items()}}


def roofline(cfg: ModelConfig, shape: InputShape, est: dict, full: dict,
             n_chips: int) -> dict:
    """All quantities from the per-device SPMD module; terms in seconds."""
    t_comp = est["flops"] / PEAK_FLOPS
    t_mem = est["bytes_accessed"] / HBM_BW
    coll_total = sum(est["collective_bytes"].values())
    t_coll = coll_total / ICI_BW
    dom = max(("compute", t_comp), ("memory", t_mem), ("collective", t_coll),
              key=lambda kv: kv[1])
    n_active = registry.n_active_params(cfg)
    if shape.mode == "train":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6 * n_active * tokens
    elif shape.mode == "prefill":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 2 * n_active * tokens
    else:
        model_flops = 2 * n_active * shape.global_batch
    hlo_total = est["flops"] * n_chips
    return {
        "compute_s": t_comp,
        "memory_s": t_mem,
        "collective_s": t_coll,
        "dominant": dom[0],
        "model_flops": model_flops,
        "hlo_flops_global": hlo_total,
        "useful_ratio": model_flops / hlo_total if hlo_total else 0.0,
        "mem_per_device_gib": (full["mem_args"] + full["mem_temp"]
                               + full["mem_out"] - full["mem_alias"])
        / 2**30,
        "fits_hbm": (full["mem_args"] + full["mem_temp"]) <= HBM_CAP,
    }


def run_combo(arch: str, shape_name: str, multi_pod: bool, outdir: str,
              rules_override=None, tag: str = "", skip_probes: bool = False):
    cfg = registry.load_config(arch)
    shape = INPUT_SHAPES[shape_name]
    reason = I.skip_reason(cfg, shape)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    key = f"{arch}_{shape_name}_{mesh_name}{tag}"
    os.makedirs(outdir, exist_ok=True)
    path = os.path.join(outdir, key + ".json")
    if reason:
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
               "skipped": reason}
        json.dump(rec, open(path, "w"), indent=1)
        print(f"[skip] {key}: {reason}")
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    # SPerf (mixtral iteration): factor the 16-way model axis into
    # (expert=8) x (model=2) so 8 experts shard instead of replicating.
    if os.environ.get("REPRO_MOE_FACTORED", "0") == "1" and cfg.n_experts \
            and cfg.n_experts < 16 and 16 % cfg.n_experts == 0:
        e = cfg.n_experts
        mshape = (2, 16, e, 16 // e) if multi_pod else (16, e, 16 // e)
        axes = ("pod", "data", "expert", "model") if multi_pod \
            else ("data", "expert", "model")
        mesh = jax.make_mesh(mshape, axes,
                             axis_types=(jax.sharding.AxisType.Auto,)
                             * len(axes))
        base = rules_for_config(cfg, mesh)
        rules_override = base.with_(experts="expert")
    rules = rules_override or rules_for_config(cfg, mesh)
    n_chips = int(np.prod(mesh.devices.shape))
    print(f"[dryrun] {key} ...", flush=True)
    full = compile_and_analyze(cfg, shape, mesh, rules)
    if skip_probes:
        est = {k: full[k] for k in
               ("flops", "bytes_accessed", "collective_bytes")}
    else:
        est = extrapolated_costs(cfg, shape, mesh, rules)
    roof = roofline(cfg, shape, est, full, n_chips)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "n_chips": n_chips, "full_compile": full, "extrapolated": est,
           "roofline": roof}
    json.dump(rec, open(path, "w"), indent=1)
    print(f"  flops/dev={est['flops']:.3e} bytes/dev={est['bytes_accessed']:.3e} "
          f"coll/dev={sum(est['collective_bytes'].values()):.3e} "
          f"dom={roof['dominant']} mem={roof['mem_per_device_gib']:.2f}GiB "
          f"(compile {full['t_compile_s']}s)", flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(INPUT_SHAPES) + [None])
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod", "both"])
    ap.add_argument("--outdir", default="experiments/dryrun")
    ap.add_argument("--skip-probes", action="store_true",
                    help="full compile only (multi-pod lowering proof)")
    args = ap.parse_args()
    archs = [args.arch] if args.arch else registry.ARCH_IDS
    shapes = [args.shape] if args.shape else list(INPUT_SHAPES)
    meshes = {"pod": [False], "multipod": [True], "both": [False, True]}[args.mesh]
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                try:
                    run_combo(arch, shape, mp, args.outdir,
                              skip_probes=args.skip_probes or mp)
                except Exception as e:  # noqa: BLE001 — report and continue
                    print(f"[FAIL] {arch} {shape} mp={mp}: {type(e).__name__}: {e}",
                          flush=True)


if __name__ == "__main__":
    main()
