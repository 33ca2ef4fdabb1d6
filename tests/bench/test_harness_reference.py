"""bench/reference.py against the program's own plain-XLA path, and the
control that every cell's check has to fail.

The reference imports nothing of the program; here, on the CPU, it is held
against ``repro.core.fitting.compute_pdf_and_error`` (the program's
reference backend) on points of each cell's cube, so a fault in either
shows. The control, the reference on bfloat16-rounded observations, must
come out as not correct under every cell's limits.
"""

import json
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, cube, reference

from repro.core import distributions as dists
from repro.core import fitting

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


def cell_parts(name):
    w = next(w for w in BENCH["workloads"] if w["name"] == name)
    cfg_file = next(c["file"] for c in BENCH["configs"] if c["name"] == w["config"])
    cfg = json.loads((ROOT / cfg_file).read_text())
    traffic = json.loads((ROOT / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    limits = json.loads((ROOT / "bench" / "workloads" / f"{name}.json").read_text())
    return cfg, traffic, limits["check"]["limits"]


def sample(cfg, redundancy, seed, per_slice=48):
    gen = cube.CubeGenerator(cube.CubeParams(
        cube.Geometry(cfg["source_num_slices"], cfg["lines_per_slice"],
                      cfg["points_per_line"]), cfg["observations"], seed, redundancy))
    rng = np.random.default_rng(seed)
    return np.concatenate([
        gen.points(s, rng.integers(0, cfg["lines_per_slice"], per_slice),
                   rng.integers(0, cfg["points_per_line"], per_slice))
        for s in cfg["set1_slices"]])


@pytest.mark.parametrize("name", CELLS)
def test_reference_agrees_with_the_programs_plain_path(name):
    cfg, traffic, limits = cell_parts(name)
    x = sample(cfg, traffic["redundancy"], seed=2**31 + 11)
    types = tuple(cfg["types"])
    m = dists.moments_from_values(jnp.asarray(x))
    r = fitting.compute_pdf_and_error(jnp.asarray(x), m, types, cfg["num_bins"])
    ans = {"type_idx": r.type_idx, "params": r.params, "error": r.error,
           "mean": m.mean, "std": m.std, "skew": m.skew, "kurt": m.kurt}
    ans = {k: np.asarray(v) for k, v in ans.items()}
    ref = reference.compute(x, types, cfg["num_bins"])
    got = check.readings(ans, ref, fit_all=True)
    assert got["unfitted"] == 0
    ok, compared = check.decide(got, {k: v for k, v in limits.items()
                                      if k not in ("missing",)})
    assert ok, compared
    np.testing.assert_array_equal(ans["type_idx"], ref["best"])


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("seed", [1, 2**31 + 3, 977])
def test_control_is_not_correct(name, seed):
    cfg, traffic, limits = cell_parts(name)
    x = sample(cfg, traffic["redundancy"], seed)
    ref = reference.compute(x, cfg["types"], cfg["num_bins"])
    ctl = check.control_answers(
        reference.compute(reference.control_values(x), cfg["types"], cfg["num_bins"]))
    got = check.readings(ctl, ref, fit_all=traffic["method"] == "baseline")
    ok, compared = check.decide(got, {k: v for k, v in limits.items()
                                      if k not in ("missing",)})
    assert not ok, compared


def test_bfloat16_rounding_is_round_half_to_even():
    x = np.array([1.0, 1.00390625, 1.01171875, 3.0e3, -2.5e-3], np.float32)
    want = np.array([1.0, 1.0, 1.015625, 3008.0, -0.002502441], np.float32)
    np.testing.assert_allclose(reference.control_values(x), want, rtol=0, atol=1e-9)
