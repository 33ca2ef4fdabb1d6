"""The comparison that decides ``correct``.

What the timed path persisted (one ``.npz`` per window and run call) is
read back once the window has closed. Every window handed back in the
window is scanned for a missing file or an unfitted point; a sample of
points drawn from the seed is compared, field by field, with the plain
reference (``bench/reference.py``) run on the same points' observations,
regenerated by ``bench/cube.py`` and not read from the program's files.

Each number is the widest gap over the sample:

* ``mean_gap``: |mean - ref| / max(|ref mean|, ref std);
* ``std_gap``: |std - ref| / ref std;
* ``skew_gap``, ``kurt_gap``: absolute gaps of g1 and excess kurtosis;
* ``params_gap``: relative gap of the three parameter slots against the
  reference's parameters of the type the program reports;
* ``error_gap``: absolute gap of the Eq.-5 error against the reference's
  error of the type the program reports; for the fit-all method, whose
  answer is the type of least error, at least the reference error of the
  reported type minus the reference's least error. For the ``*_ml``
  methods the type is the tree's prediction, which the reference cannot
  make without the program's tree, so only its parameters and error are
  held to the reference;
* ``missing``, ``unfitted``: windows handed back with no persisted file,
  and points with no valid type or a non-finite field (limit 0).

A NaN gap reads as infinite. The limits per cell live in
``bench/workloads/<cell>.json``; a number without a limit there is printed
but not compared.
"""

from __future__ import annotations

from pathlib import Path

import numpy as np

from bench import reference

FIELDS = ("type_idx", "params", "error", "mean", "std", "skew", "kurt")


def _gap(a, b, scale=None) -> float:
    d = np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))
    if scale is not None:
        d = d / scale
    if d.size == 0:
        return 0.0
    d = np.where(np.isnan(d), np.inf, d)
    return float(d.max())


def readings(ans: dict, ref: dict, fit_all: bool) -> dict[str, float]:
    """``ans``: per-point answers (k,) / (k, 3); ``ref``: reference.compute
    on the same points. Points with an invalid type count in ``unfitted``
    and are left out of the gaps."""
    t = np.asarray(ans["type_idx"], np.int64)
    num_types = ref["errors"].shape[1]
    ok = (t >= 0) & (t < num_types)
    for name in FIELDS[1:]:
        v = np.asarray(ans[name], np.float64)
        ok &= np.isfinite(v.reshape(len(t), -1)).all(axis=1)
    out = {"unfitted": float((~ok).sum())}
    t, rows = t[ok], np.nonzero(ok)[0]

    def pick(name):
        return np.asarray(ans[name])[rows]

    mean_r, std_r = ref["mean"][ok], ref["std"][ok]
    out["mean_gap"] = _gap(pick("mean"), mean_r, np.maximum(np.abs(mean_r), std_r))
    out["std_gap"] = _gap(pick("std"), std_r, std_r)
    out["skew_gap"] = _gap(pick("skew"), ref["skew"][ok])
    out["kurt_gap"] = _gap(pick("kurt"), ref["kurt"][ok])
    p_ref = ref["params"][ok][np.arange(len(t)), t]  # (k, 3)
    scale = np.abs(p_ref)
    p = np.asarray(pick("params"), np.float64)
    both_zero = (p == 0) & (p_ref == 0)
    rel = np.where(both_zero, 0.0, np.abs(p - p_ref) / np.where(scale > 0, scale, 1e-300))
    out["params_gap"] = _gap(rel, 0.0)
    e_ref = ref["errors"][ok][np.arange(len(t)), t]
    gap = np.abs(np.asarray(pick("error"), np.float64) - e_ref)
    if fit_all:
        # the answer is the type of least error: a reported type whose
        # reference error lies above the least one counts that excess too
        gap = np.maximum(gap, e_ref - ref["errors"][ok].min(axis=1))
    out["error_gap"] = _gap(gap, 0.0)
    return out


def control_answers(ref_ctl: dict) -> dict:
    """The control put in the program's place: the reference computed on
    bfloat16-rounded observations, reporting its own least-error type."""
    k = ref_ctl["best"]
    rows = np.arange(len(k))
    return {
        "type_idx": k, "params": ref_ctl["params"][rows, k],
        "error": ref_ctl["errors"][rows, k], "mean": ref_ctl["mean"],
        "std": ref_ctl["std"], "skew": ref_ctl["skew"], "kurt": ref_ctl["kurt"],
    }


def window_file(call_dir: Path, slice_i: int, line_start: int) -> Path:
    return Path(call_dir) / f"slice{slice_i}_window_{line_start:05d}.npz"


def scan(handed_back) -> dict[str, float]:
    """Every window handed back in the window: a persisted file with one
    valid answer per point. ``handed_back``: (call_dir, slice, line_start,
    num_points). ``bad_windows`` counts the windows with either fault."""
    missing = unfitted = bad_windows = 0
    for call_dir, s, line_start, num_points in handed_back:
        f = window_file(call_dir, s, line_start)
        if not f.exists():
            missing += 1
            bad_windows += 1
            continue
        with np.load(f) as z:
            t = z["type_idx"]
            bad = t < 0
            for name in FIELDS[1:]:
                v = z[name].reshape(len(t), -1)
                bad = bad | ~np.isfinite(v).all(axis=1)
            n_bad = int(np.sum(bad)) + abs(num_points - len(t))
        unfitted += n_bad
        bad_windows += n_bad > 0
    return {"missing": float(missing), "unfitted": float(unfitted),
            "bad_windows": float(bad_windows)}


def sample_points(rng: np.random.Generator, handed_back, windows: int,
                  per_window: int):
    """Draw ``windows`` of the handed-back windows and ``per_window`` points
    in each, from ``rng``: (call_dir, slice, line_start, rows in window)."""
    n = len(handed_back)
    picks = rng.choice(n, size=min(windows, n), replace=False)
    out = []
    for i in sorted(picks):
        call_dir, s, line_start, num_points = handed_back[i]
        rows = np.sort(rng.choice(num_points, size=min(per_window, num_points),
                                  replace=False))
        out.append((call_dir, s, line_start, rows))
    return out


def load_answers(sample) -> dict:
    """Persisted answers of the sampled points, in sample order."""
    parts = {name: [] for name in FIELDS}
    for call_dir, s, line_start, rows in sample:
        with np.load(window_file(call_dir, s, line_start)) as z:
            for name in FIELDS:
                parts[name].append(z[name][rows])
    return {name: np.concatenate(v) for name, v in parts.items()}


def observations(gen, set1_slices, sample, ppl: int) -> np.ndarray:
    """The sampled points' observations, regenerated from the seed."""
    blocks = []
    for _call, s, line_start, rows in sample:
        lines = line_start + rows // ppl
        blocks.append(gen.points(set1_slices[s], lines, rows % ppl))
    return np.concatenate(blocks)


def decide(numbers: dict[str, float], limits: dict[str, float]):
    """-> (correct, compared): every number with a limit must not exceed it."""
    compared = {k: {"value": numbers[k], "limit": limits[k]}
                for k in limits if k in numbers}
    missing = [k for k in limits if k not in numbers]
    if missing:
        raise KeyError(f"limits name numbers the check does not compute: {missing}")
    ok = all(v["value"] <= v["limit"] for v in compared.values())
    return ok, compared


def reference_numbers(gen, cfg: dict, answers: dict, sample, fit_all: bool,
                      control: bool = False) -> dict[str, float]:
    """Readings of the program's answers (or, with ``control``, of the
    bfloat16 control's) against the float64 reference."""
    ppl = cfg["points_per_line"]
    x = observations(gen, cfg["set1_slices"], sample, ppl)
    ref = reference.compute(x, cfg["types"], cfg["num_bins"])
    if control:
        answers = control_answers(
            reference.compute(reference.control_values(x), cfg["types"], cfg["num_bins"]))
    return readings(answers, ref, fit_all)
