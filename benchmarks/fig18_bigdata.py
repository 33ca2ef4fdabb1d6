"""Fig. 18/19/20 analog: the TB-scale regimes.

Set2 (1.9 TB): more points, 1000 obs — ML scales, Grouping hurt by shuffle.
Set3 (2.4 TB): 10x observations per point — Grouping's shuffle payload is
9x bigger (the paper drops Grouping entirely); ML keeps its advantage.

Reduced here: 'obs_1x' ~ Set1/2 regime vs 'obs_10x' ~ Set3 regime, same
points. Derived: grouping's advantage collapsing when the per-point payload
grows 10x while ML's advantage persists."""

from __future__ import annotations

import os
import re
import socket
import subprocess
import sys
import tempfile
import time
from pathlib import Path

from repro.core import distributions as d
from benchmarks.common import Row, run_method, small_sim, train_type_tree


def run(quick: bool = True):
    rows = []
    summary = {}
    for obs, tag in [(150 if quick else 1000, "obs_1x"), (1500 if quick else 10000, "obs_10x")]:
        sim = small_sim(lines=8, ppl=30, num_simulations=obs)
        tree = train_type_tree(sim, window_lines=4)
        res_b, _ = run_method(sim, "baseline", d.TYPES_4, 4, 2)
        res_g, _ = run_method(sim, "grouping", d.TYPES_4, 4, 2)
        res_m, _ = run_method(sim, "ml", d.TYPES_4, 4, 2, tree=tree)
        cb, cg, cm = (
            r.total_compute_seconds for r in (res_b, res_g, res_m)
        )
        # grouping "shuffle" payload analog: bytes of observation data moved
        # for representative re-dispatch (the host->device second pass)
        payload = sum(s.num_fitted for s in res_g.stats) * obs * 4
        summary[tag] = (cb / cg, cb / cm)
        rows.append(Row(f"fig18/{tag}/baseline", cb * 1e6, "",
                        spec_hash=res_b.spec_hash or ""))
        rows.append(Row(f"fig18/{tag}/grouping", cg * 1e6,
                        f"speedup={cb/cg:.2f}x payload={payload/1e6:.1f}MB",
                        spec_hash=res_g.spec_hash or ""))
        rows.append(Row(f"fig18/{tag}/ml", cm * 1e6, f"speedup={cb/cm:.2f}x",
                        spec_hash=res_m.spec_hash or ""))
    g1, m1 = summary["obs_1x"]
    g10, m10 = summary["obs_10x"]
    rows.append(
        Row("fig18/grouping_vs_obs_scale", 0.0,
            f"grouping {g1:.2f}x->{g10:.2f}x ml {m1:.2f}x->{m10:.2f}x "
            "(paper: grouping COLLAPSES at 10x obs because Spark shuffles "
            "whole observation vectors; our shuffle moves (mu,sigma) keys + "
            "representative rows only, so grouping survives Set3 — an "
            "intentional substrate improvement, see EXPERIMENTS.md)")
    )
    rows.extend(weak_scaling_rows())
    return rows


def weak_scaling_rows() -> list[Row]:
    """``cluster/weak_scaling_{N}proc``: N real ``run_pdf`` worker processes
    (one ``jax.distributed`` seat each, 1 CPU device each) over N slices —
    fixed work per process, wall clock per whole launch. The paper's
    weak-scaling shape (Fig. 13 at cluster granularity). Tracked, NOT gated:
    interpreter startup dominates at this reduced scale, so the row's value
    is trend visibility — a topology regression (workers serializing on a
    peer's shard, the marker protocol blocking the exit path) shows up as a
    wall-time jump against the per-process baseline."""
    repo = Path(__file__).resolve().parent.parent
    env = dict(os.environ, PYTHONPATH=str(repo / "src"),
               JAX_PLATFORMS="cpu", JAX_NUM_CPU_DEVICES="1",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    rows: list[Row] = []
    base_wall = None
    # No --compile-cache-dir: every worker shares the launcher's fixed
    # compile cache (JAX_COMPILATION_CACHE_DIR, else .jax_cache/ in the
    # checkout), so the rows measure the run, not XLA.
    with tempfile.TemporaryDirectory() as tmp:
        for nprocs in (1, 2, 4):
            with socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                coord = f"127.0.0.1:{s.getsockname()[1]}"
            flags = [
                "--num-slices", str(nprocs), "--lines", "6", "--ppl", "10",
                "--obs", "80", "--method", "grouping", "--window-lines", "3",
                "--num-bins", "20", "--slices",
                *[str(i) for i in range(nprocs)],
                "--out-dir", str(Path(tmp) / f"out{nprocs}"),
                "--num-processes", str(nprocs), "--coordinator", coord,
            ]
            t0 = time.perf_counter()
            procs = [subprocess.Popen(
                [sys.executable, "-m", "repro.launch.run_pdf", *flags,
                 "--process-id", str(i)],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True) for i in range(nprocs)]
            outs = [p.communicate()[0] for p in procs]
            wall = time.perf_counter() - t0
            name = f"cluster/weak_scaling_{nprocs}proc"
            if any(p.returncode != 0 for p in procs):
                rows.append(Row(name, 0.0,
                                "SKIPPED: worker failed (platform cannot "
                                "run a jax.distributed coordinator)"))
                continue
            if base_wall is None:
                base_wall = wall
            eff = base_wall / wall if wall > 0 else 0.0
            m = re.search(r"hash=([0-9a-f]{16})", outs[0])
            rows.append(Row(
                name, wall * 1e6,
                f"efficiency={eff:.2f} (1.0 = perfect weak scaling)",
                spec_hash=m.group(1) if m else ""))
    return rows
