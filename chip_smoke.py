"""Chip smoke: the batch PDF pipeline end to end on a TPU, at the paper's
Set1 shapes.

    python chip_smoke.py               # main phase, one chip
    python chip_smoke.py --four-chips  # shard-per-chip placement, four chips

Main phase: ``configs/pdf_seismic.to_spec(SET1)`` — slice 201,
``grouping_ml``, 4 candidate types, 25-line windows (6,275 points x 1,000
observations each), 20 bins — runs through ``PDFSession`` the way
``launch/run_pdf.py`` runs it: 21 windows, about 503 MB of observations,
after training the decision tree the spec's ``TreeSpec`` describes. The
same slice then runs with ``fit_backend="reference"`` and every point is
compared with the tolerances of ``tests/test_fit_backends.py``. Last, one
window's grouping keys are computed on the device and on the host, and
``select_backend="device"`` must refuse to run on a TPU, whose emulated
float64 cannot reproduce the host keys.

``--four-chips`` runs only Set1 slices 200-203 with one shard pinned to
each chip (``ExecSpec.placement.shard_devices``), and the same slices on
one chip, and asserts the results are bitwise identical and that every
shard's windows were staged and fitted on its own chip.

Every phase prints its device, times, compile counters and peak device
memory. The last line of standard output is one JSON object, printed only
when every check passed. The script exits non-zero, and prints no such
line, when JAX finds no TPU, when a window is quarantined or a point is
left unfitted, when a result disagrees with its reference, or when any
phase raises. It runs in one process and starts no other.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.api import PDFSession, PipelineSpec  # noqa: E402
from repro.configs import pdf_seismic  # noqa: E402
from repro.core import fitting  # noqa: E402
from repro.core import grouping as grp  # noqa: E402
from repro.core import regions  # noqa: E402
from repro.core.executor import DEVICE_SELECT_REFUSED_PLATFORMS  # noqa: E402
from repro.runtime.cluster import verify_outputs  # noqa: E402

OUT = ROOT / "results" / "chip_smoke"

# (field, rtol, atol) as asserted in tests/test_fit_backends.py; type_idx
# must match exactly.
TOLERANCES = (
    ("error", 0.0, 2e-3),
    ("params", 2e-3, 2e-3),
    ("mean", 1e-3, 1e-2),
    ("std", 2e-2, 1e-2),
)
RESULT_FIELDS = ("type_idx", "params", "error", "mean", "std", "skew", "kurt")


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def with_execution(spec: PipelineSpec, **kw) -> PipelineSpec:
    return dataclasses.replace(
        spec, execution=dataclasses.replace(spec.execution, **kw))


def with_compute(spec: PipelineSpec, **kw) -> PipelineSpec:
    return dataclasses.replace(
        spec, compute=dataclasses.replace(spec.compute, **kw))


def device_fields() -> str:
    d = jax.devices()[0]
    return f"platform={d.platform} kind={d.device_kind!r} count={len(jax.devices())}"


def peak_bytes() -> dict:
    """``peak_bytes_in_use`` per device, where the backend reports it."""
    out = {}
    for d in jax.devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            out[d.id] = int(stats["peak_bytes_in_use"])
    return out


def run_session(label: str, spec: PipelineSpec, tree=None,
                on_executor=None) -> tuple[PDFSession, dict]:
    """One ``PDFSession`` run, as ``launch/run_pdf.py:_run_once`` drives
    it, into a fresh ``out_dir``; fails on any quarantined window or
    unfitted point. ``on_executor(shard, executor)`` sees each shard's
    executor before it runs."""
    if spec.execution.out_dir:
        shutil.rmtree(spec.execution.out_dir, ignore_errors=True)
    session = PDFSession(spec, tree=tree)
    t0 = time.perf_counter()
    tree = session.tree
    tree_s = time.perf_counter() - t0
    if on_executor is not None:
        for shard in range(spec.execution.shards):
            on_executor(shard, session.executor(shard))
    t0 = time.perf_counter()
    results = {r.slice_i: r for r in session.run()}
    wall = time.perf_counter() - t0
    rep = session.report()
    check(rep.quarantined_units == 0,
          f"{label}: {rep.quarantined_units} unit(s) quarantined")
    for s, r in results.items():
        check(not r.degraded, f"{label}: slice {s} degraded: {r.quarantined}")
        check(bool((r.type_idx >= 0).all()),
              f"{label}: slice {s} has {(r.type_idx < 0).sum()} unfitted points")
    fitted = sum(w.num_fitted for r in results.values() for w in r.stats)
    points = sum(len(r.type_idx) for r in results.values())
    print(f"[phase {label}] {device_fields()} wall_s={wall} tree_s={tree_s} "
          f"slices={sorted(results)} windows={rep.windows} points={points} "
          f"fitted={fitted} traces={rep.traces} compiles={rep.compiles} "
          f"compile_cache_hits={rep.compile_cache_hits} "
          f"compile_cache_misses={rep.compile_cache_misses} "
          f"compile_cache_dir={session.compile_cache_dir} "
          f"peak_bytes_in_use={peak_bytes()}", flush=True)
    return session, results


def compare_to_reference(label: str, ref, got) -> None:
    """Per point, ``got`` against the reference backend's ``ref`` with the
    tests/test_fit_backends.py tolerances (NaNs must sit in the same
    places)."""
    type_mismatch = int((ref.type_idx != got.type_idx).sum())
    parts = [f"type_idx_mismatch={type_mismatch}"]
    bad = type_mismatch
    for name, rtol, atol in TOLERANCES:
        a = np.asarray(getattr(ref, name), np.float64)
        b = np.asarray(getattr(got, name), np.float64)
        nan = np.isnan(a) | np.isnan(b)
        both_nan = np.isnan(a) & np.isnan(b)
        diff = np.where(nan, 0.0, np.abs(a - b))
        over = (diff > atol + rtol * np.abs(np.where(nan, 0.0, b))) | (nan & ~both_nan)
        bad += int(over.sum())
        parts.append(f"max_abs_diff_{name}={float(diff.max())} over_tol_{name}={int(over.sum())}")
    print(f"[compare {label}] " + " ".join(parts), flush=True)
    check(bad == 0, f"{label}: results outside the test_fit_backends tolerances")


def device_select_check(spec: PipelineSpec, tree) -> None:
    """One window's grouping keys on this device against the host's. Where
    device Select may run, its partition and per-point results must equal
    host Select's bitwise; on a TPU it must refuse with a clear error."""
    host_spec = with_execution(spec, out_dir=None)
    dev_spec = with_compute(host_spec, select_backend="device")
    host_session = PDFSession(host_spec, tree=tree)
    w = next(regions.iter_windows(host_session.geometry, spec.execution.slices[0],
                                  spec.compute.window_lines))
    tol = spec.method.group_tol
    backend = fitting.get_fit_backend(spec.compute.fit_backend, spec.compute.num_bins)
    m = jax.jit(backend.moments)(jnp.asarray(host_session.source.load_window(w)))
    host_keys = grp.quantize_keys_host(np.asarray(m.mean), np.asarray(m.var), tol)
    dev_keys = grp.keys_to_int64(np.asarray(jax.jit(
        lambda mean, var: grp.quantize_keys_from_var(mean, var, tol))(m.mean, m.var)))
    host = grp.group_host(host_keys)
    dev = grp.group_device(jnp.asarray(grp.quantize_keys_from_var(m.mean, m.var, tol)))
    moved = int((host.rep_indices[host.inverse] != np.asarray(dev.rep_for_point)).sum())
    print(f"[phase device_select] {device_fields()} window={tuple(w)} "
          f"keys_off_host={int(np.any(dev_keys != host_keys, axis=1).sum())}"
          f"/{len(host_keys)} host_groups={host.num_groups} "
          f"device_groups={int(dev.num_groups)} points_in_other_group={moved}",
          flush=True)
    platform = jax.devices()[0].platform
    if platform in DEVICE_SELECT_REFUSED_PLATFORMS:
        try:
            PDFSession(dev_spec, tree=tree).executor(0)
        except ValueError as e:
            check("select_backend='device' is refused" in str(e),
                  f"device Select failed with an unexpected error: {e}")
            print(f"[compare device_select] refused_on={platform}", flush=True)
            return
        raise SmokeFailure(f"device Select ran on {platform} instead of refusing")
    check(moved == 0 and int(dev.num_groups) == host.num_groups,
          "device Select partitions the window differently from host Select")
    a = host_session.executor(0).run_window(w)
    b = PDFSession(dev_spec, tree=tree).executor(0).run_window(w)
    for name in RESULT_FIELDS:
        check(np.array_equal(getattr(a, name), getattr(b, name), equal_nan=True),
              f"device Select {name} differs from host Select")
    print("[compare device_select] bitwise_equal=True", flush=True)


def main_phase(spec: PipelineSpec, out: Path = OUT) -> None:
    """Fused run, reference run, per-point comparison, device Select."""
    spec = with_execution(spec, out_dir=str(out / "fused"), degraded_mode=False)
    session, fused = run_session("fused", spec)
    ref_spec = with_execution(with_compute(spec, fit_backend="reference"),
                              out_dir=str(out / "reference"))
    _, ref = run_session("reference", ref_spec, tree=session.tree)
    for s in fused:
        compare_to_reference(f"fused_vs_reference slice={s}", ref[s], fused[s])
    device_select_check(spec, session.tree)


def four_chip_phase(spec: PipelineSpec, out: Path = OUT) -> None:
    """``spec``'s slices on one chip, then dealt to four shards pinned to
    chips 0-3: bitwise-equal results, each shard's arrays on its chip."""
    n = len(jax.devices())
    check(n >= 4, f"--four-chips needs 4 devices, JAX sees {n}")
    spec = with_execution(spec, degraded_mode=False)
    staged: dict[int, set] = {}

    def record_devices(shard, ex):
        # StagedExecutor's stats_recorder seam sees every window's staged
        # values and moments before the fit consumes them.
        def recorder(w, values, moments):
            staged.setdefault(shard, set()).update(
                d.id for a in (values, moments[0]) for d in a.devices())
        ex.stats_recorder = recorder

    one_spec = with_execution(spec, out_dir=str(out / "one_chip"))
    session, one = run_session("one_chip", one_spec, on_executor=record_devices)
    check(staged == {0: {jax.devices()[0].id}},
          f"one-chip run staged windows on devices {staged}")
    staged.clear()
    placement = dataclasses.replace(spec.execution.placement,
                                    shard_devices=(0, 1, 2, 3))
    four_spec = with_execution(spec, shards=4, placement=placement,
                               out_dir=str(out / "four_chips"))
    _, four = run_session("four_chips", four_spec, tree=session.tree,
                          on_executor=record_devices)
    local = jax.local_devices()
    want = {k: {local[k].id} for k in range(4)}
    print(f"[placement] staged_devices={ {k: sorted(v) for k, v in staged.items()} } "
          f"expected={ {k: sorted(v) for k, v in want.items()} }", flush=True)
    check(staged == want, "a shard's windows left its own device")
    check(sorted(one) == sorted(four), "the two runs covered different slices")
    for s in one:
        for name in RESULT_FIELDS:
            check(np.array_equal(getattr(one[s], name), getattr(four[s], name),
                                 equal_nan=True),
                  f"slice {s} {name}: four chips differ from one chip")
    windows, arrays = verify_outputs(out / "one_chip", out / "four_chips")
    print(f"[compare four_chips_vs_one_chip] bitwise_equal=True "
          f"persisted_windows={windows} arrays={arrays}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the shard-per-chip placement against one chip")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform={dev.platform})",
              file=sys.stderr)
        return 1
    set1 = pdf_seismic.to_spec(pdf_seismic.SET1)
    if args.four_chips:
        four_chip_phase(with_execution(set1, slices=(200, 201, 202, 203)))
    else:
        main_phase(set1)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
