import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Dry-run of the PAPER's own workload: one PDF-computation window step on
the production mesh (the analog of launch/dryrun.py for the LM cells).

The step is the fused device part of Algorithms 1-3 for a window of points:
moments -> fit all candidate types -> Eq.-5 error -> argmin (plus, in the
``grouping_global`` variant, the §5.2 cross-device shuffle via all_gather,
whose collective term is exactly the paper's "grouping stops scaling"
effect).

Variants (--variant):
  faithful        baseline per-type histogram passes (paper cost model)
  fused           shared histogram across types (beyond-paper optimization)
  grouping_global faithful + global grouping shuffle (collective exposure)

Shapes (--pdf-shape):
  window_small    6,275 pts x 1,000 obs   (Set1: 25 lines x 251 points)
  window_prod     262,144 pts x 1,000 obs (Set2-scale, mesh-sized window)
  window_obs10k   65,536 pts x 10,000 obs (Set3 regime: 10x observations)

  PYTHONPATH=src python -m repro.launch.dryrun_pdf --all --out results/dryrun_pdf
"""

import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import distributions as d
from repro.core import fitting
from repro.core import grouping as grp
from repro.launch import roofline as rl
from repro.launch.mesh import make_production_mesh

PDF_SHAPES = {
    # Set1 window (25 lines x 251 points = 6,275) padded to the 512-device
    # mesh divisor, as the loader does (data/loader.ShardedStager).
    "window_small": (6_656, 1_000),
    "window_prod": (262_144, 1_000),
    "window_obs10k": (65_536, 10_000),
}

VARIANTS = ("faithful", "fused", "fused_scatter", "fused_scatter_shmap", "grouping_global")
NUM_BINS = 20
TYPES = d.TYPES_4

# Pipeline knobs (types / num_bins / group_tol) come from the shared
# PipelineSpec surface — the dry-run declares only its own defaults here
# (the paper's 20-bin histogram) and no flags of its own for them, so it can
# never again drift from the launchers (PR 3 had to fix this file silently
# dropping --group-tol).
def _base_spec():
    from repro.api import ComputeSpec, PipelineSpec

    return PipelineSpec(compute=ComputeSpec(num_bins=NUM_BINS, types=TYPES))


def make_window_step(variant: str, mesh, types=TYPES, num_bins=NUM_BINS,
                     group_tol: float = grp.DEFAULT_TOL):
    axes = tuple(mesh.axis_names)

    def core(values):
        from repro.core import pdf_error as pe

        m = d.moments_from_values(values)
        mode = "faithful" if variant in ("faithful", "grouping_global") else "fused"
        hist = (
            pe.histogram_scatter
            if variant.startswith("fused_scatter")
            else pe.histogram
        )
        r = fitting.compute_pdf_and_error(
            values, m, types, num_bins, mode=mode, histogram_fn=hist
        )
        return (r.type_idx, r.params, r.error, m.mean, m.var)

    if variant == "fused_scatter_shmap":
        # The per-point fit is embarrassingly parallel (the paper's Map):
        # shard_map makes that explicit, so the partitioner cannot introduce
        # data gathers (§Perf pdf-seismic iteration 3).
        return jax.shard_map(
            core, mesh=mesh,
            in_specs=P(axes, None),
            out_specs=(P(axes), P(axes, None), P(axes), P(axes), P(axes)),
        )

    def step(values):
        out = core(values)
        if variant == "grouping_global":
            # §5.2 global shuffle: quantized keys all_gathered + dedup'd.
            # quantize_keys_from_var matches the host Select path bit-exactly
            # (f64 sqrt + hi/lo int32 key pairs) at the *configured* tol —
            # this used to drop the tolerance and always group at DEFAULT_TOL.
            mean, var = out[3], out[4]
            keys = grp.quantize_keys_from_var(mean, var, tol=group_tol)
            rep = jax.shard_map(
                lambda k: grp.group_device_global(k, axes).rep_for_point,
                mesh=mesh, in_specs=P(axes), out_specs=P(axes),
            )(keys)
            out = out + (rep,)
        return out

    return step


def run_pdf_cell(variant: str, shape_name: str, mesh, verbose=True,
                 group_tol: float = grp.DEFAULT_TOL, types=TYPES,
                 num_bins: int = NUM_BINS, spec_hash: str | None = None) -> dict:
    points, obs = PDF_SHAPES[shape_name]
    chips = mesh.devices.size
    axes = tuple(mesh.axis_names)
    values = jax.ShapeDtypeStruct((points, obs), jnp.float32)
    in_sh = NamedSharding(mesh, P(axes, None))

    step = make_window_step(variant, mesh, types=types, num_bins=num_bins,
                            group_tol=group_tol)
    t0 = time.perf_counter()
    lowered = jax.jit(step, in_shardings=(in_sh,)).lower(values)
    compiled = lowered.compile()
    t1 = time.perf_counter()

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    coll = rl.parse_collectives(compiled.as_text(), chips)
    flops_dev = float(cost.get("flops", 0.0))
    bytes_dev = float(cost.get("bytes accessed", 0.0))

    # "model flops" for the PDF step: the minimum useful work = one moments
    # pass (5 flops/value) + one histogram pass (2) + T x O(L) CDF math.
    t_types = len(types)
    model_flops = points * obs * (5.0 + 2.0) + points * t_types * num_bins * 25.0
    roof = rl.make_roofline(flops_dev, bytes_dev, coll, chips, model_flops)

    rec = {
        "workload": "pdf-seismic",
        "variant": variant,
        "spec_hash": spec_hash,
        "shape": shape_name,
        "points": points,
        "obs": obs,
        "mesh": list(mesh.devices.shape),
        "chips": chips,
        "ok": True,
        "compile_seconds": round(t1 - t0, 2),
        "flops_per_device": flops_dev,
        "bytes_per_device": bytes_dev,
        "collective_traffic_per_device": coll.per_device_traffic_bytes,
        "collective_ops": coll.op_counts,
        "memory_analysis": {
            "argument_size_in_bytes": getattr(mem, "argument_size_in_bytes", None),
            "temp_size_in_bytes": getattr(mem, "temp_size_in_bytes", None),
        },
        "model_flops": model_flops,
        "terms_seconds": {
            "compute": roof.compute_s,
            "memory": roof.memory_s,
            "collective": roof.collective_s,
        },
        "dominant": roof.dominant,
        "useful_ratio": roof.useful_ratio,
        "roofline_fraction": roof.roofline_fraction,
    }
    if verbose:
        t = rec["terms_seconds"]
        print(f"[pdf {variant} x {shape_name} x {'x'.join(map(str, mesh.devices.shape))}] "
              f"compile {rec['compile_seconds']}s")
        print(f"  flops/dev {flops_dev:.3e} bytes/dev {bytes_dev:.3e} "
              f"coll/dev {coll.per_device_traffic_bytes:.3e} {coll.op_counts}")
        print(f"  compute {t['compute']*1e3:.2f}ms memory {t['memory']*1e3:.2f}ms "
              f"collective {t['collective']*1e3:.2f}ms -> {rec['dominant']} "
              f"(useful {roof.useful_ratio:.3f})")
    return rec


def main():
    from repro.api import add_spec_args, spec_from_args

    ap = argparse.ArgumentParser()
    ap.add_argument("--variant", choices=VARIANTS, default=None)
    ap.add_argument("--pdf-shape", choices=list(PDF_SHAPES), default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="results/dryrun_pdf",
                    help="directory for per-cell roofline records")
    # every pipeline knob (--group-tol, --types, --num-bins, --spec ...)
    # comes from the shared spec surface
    add_spec_args(ap)
    args = ap.parse_args()
    spec = spec_from_args(args, base=_base_spec())
    print(f"[spec] hash={spec.content_hash()} source={spec.source.kind} "
          f"types={len(spec.compute.types)} bins={spec.compute.num_bins} "
          f"group_tol={spec.method.group_tol}")

    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    mesh = make_production_mesh(multi_pod=args.multi_pod)
    variants = VARIANTS if (args.all or not args.variant) else [args.variant]
    shapes = list(PDF_SHAPES) if (args.all or not args.pdf_shape) else [args.pdf_shape]

    failures = []
    for v in variants:
        for s in shapes:
            cid = f"pdf__{v}__{s}__{'pod2' if args.multi_pod else 'pod1'}"
            try:
                rec = run_pdf_cell(
                    v, s, mesh,
                    group_tol=spec.method.group_tol,
                    types=tuple(spec.compute.types),
                    num_bins=spec.compute.num_bins,
                    spec_hash=spec.content_hash(),
                )
            except Exception as e:  # noqa: BLE001
                traceback.print_exc()
                rec = {"ok": False, "variant": v, "shape": s, "error": str(e)}
                failures.append(cid)
            (out / f"{cid}.json").write_text(json.dumps(rec, indent=1))
    if failures:
        raise SystemExit(f"failed: {failures}")
    print("pdf dry-run complete")


if __name__ == "__main__":
    main()
