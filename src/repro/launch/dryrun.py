"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this prints/records:
  * compiled.memory_analysis()  — proves the cell fits per-device HBM
  * compiled.cost_analysis()    — HLO FLOPs / bytes for §Roofline
  * the collective-op byte census parsed from the compiled HLO text

Results land in experiments/dryrun/<mesh>/<arch>__<shape>.json and are
aggregated by repro.roofline.analysis into EXPERIMENTS.md tables.

Usage:
  python -m repro.launch.dryrun --arch yi-6b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all [--multi-pod]
"""

import argparse
import json
import os
import time
import traceback


def cell_skip_reason(cfg, shape_name: str):
    from repro.configs.base import SHAPES

    shape = SHAPES[shape_name]
    if shape_name == "long_500k" and not cfg.sub_quadratic:
        return (
            "long_500k needs sub-quadratic attention; "
            f"{cfg.name} is pure full-attention (DESIGN.md §5)"
        )
    return None


def run_cell(arch: str, shape_name: str, multi_pod: bool, outdir: str,
             overrides=None) -> dict:
    import jax
    import numpy as np

    from repro.configs.base import SHAPES, get_config
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import build
    from repro.roofline.analysis import collective_census, roofline_terms
    from repro.roofline.hlo_cost import analyze_hlo

    cfg = get_config(arch)
    if overrides:
        cfg = cfg.replace(**overrides)
    shape = SHAPES[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "mode": shape.mode, "status": "ok",
        "overrides": {k: str(v) for k, v in (overrides or {}).items()},
    }
    skip = cell_skip_reason(cfg, shape_name)
    if skip:
        rec.update(status="skip", reason=skip)
        _write(outdir, mesh_name, arch, shape_name, rec, overrides)
        print(f"[SKIP] {arch} x {shape_name}: {skip}")
        return rec
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        bundle = build(cfg, mesh, shape)
        lowered = bundle.lower()
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        print(mem)
        ca = compiled.cost_analysis()
        print({k: ca.get(k) for k in ("flops", "bytes accessed")})
        hlo = compiled.as_text()
        census = collective_census(hlo)  # single-pass (loop bodies once)
        cost = analyze_hlo(hlo)  # loop-aware: trip-count multiplied
        n_chips = int(np.prod(list(mesh.shape.values())))
        rec.update(
            seconds_lower=round(t_lower, 1),
            seconds_compile=round(t_compile, 1),
            n_chips=n_chips,
            # loop-aware GLOBAL totals: the compiled HLO is the per-device
            # SPMD program, so x n_chips (cost_analysis also counts while
            # bodies once — see roofline/hlo_cost.py); raw kept for reference
            # flops: loop-aware dot/MXU flops (elementwise excluded — the
            # MFU convention).  bytes: loop-aware operand+result bytes at
            # the CPU backend's fusion granularity — an upper bound on TPU
            # HBM traffic (TPU fuses more); relative comparisons between
            # variants of the same cell are reliable (see roofline docs).
            flops=float(cost["flops"]) * n_chips,
            bytes_accessed=float(cost["bytes"]) * n_chips,
            loop_bytes_factor=float(cost["loop_bytes_factor"]),
            flops_raw_costanalysis=float(ca.get("flops", 0.0)),
            bytes_raw_costanalysis=float(ca.get("bytes accessed", 0.0)),
            memory={
                "argument_size": mem.argument_size_in_bytes,
                "output_size": mem.output_size_in_bytes,
                "temp_size": mem.temp_size_in_bytes,
                "alias_size": mem.alias_size_in_bytes,
                "generated_code_size": mem.generated_code_size_in_bytes,
            },
            collectives={
                "per_kind": cost["per_kind"],
                "wire_bytes_per_chip": cost["wire_bytes_per_chip"],
                "single_pass": census,
            },
            params=int(sum(
                int(np.prod(x.shape))
                for x in jax.tree_util.tree_leaves(bundle.params_sds)
            )),
            params_active=cfg.active_param_count(),
            tokens=shape.global_batch * (shape.seq_len if shape.mode != "decode" else 1),
            attention_schedule=cfg.attention_schedule,
            remat=cfg.remat,
            microbatches=shape.microbatches if shape.mode == "train" else 1,
        )
        rec["roofline"] = roofline_terms(rec)
        print(
            f"[OK] {arch} x {shape_name} ({mesh_name}): "
            f"lower {t_lower:.0f}s compile {t_compile:.0f}s  "
            f"flops {rec['flops']:.3g}  coll_bytes {census['wire_bytes_per_chip']:.3g}"
        )
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
        print(f"[ERR] {arch} x {shape_name}: {e}")
    _write(outdir, mesh_name, arch, shape_name, rec, overrides)
    return rec


def _write(outdir, mesh_name, arch, shape_name, rec, overrides=None):
    d = os.path.join(outdir, mesh_name)
    os.makedirs(d, exist_ok=True)
    tag = ""
    if overrides:
        tag = "__" + "_".join(f"{k}={v}" for k, v in sorted(overrides.items()))
        tag = tag.replace("/", "-")[:80]
    with open(os.path.join(d, f"{arch}__{shape_name}{tag}.json"), "w") as f:
        json.dump(rec, f, indent=1)


def main():
    # 512 host devices stand in for the pod.  Set here, not on import,
    # and added to the caller's XLA_FLAGS: jax fixes the device count
    # when it first initializes, which happens after this in run_cell.
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=512"
    ).strip()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--outdir", default="experiments/dryrun")
    ap.add_argument("--set", action="append", default=[],
                    help="config override key=value (e.g. attention_schedule=bb)")
    args = ap.parse_args()

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        try:
            v = int(v)
        except ValueError:
            pass
        overrides[k] = v

    from repro.configs.ALL import ARCH_IDS
    from repro.configs.base import SHAPES

    if args.all:
        for arch in ARCH_IDS:
            for shape in SHAPES:
                run_cell(arch, shape, args.multi_pod, args.outdir,
                         overrides or None)
    else:
        run_cell(args.arch, args.shape, args.multi_pod, args.outdir,
                 overrides or None)


if __name__ == "__main__":
    main()
