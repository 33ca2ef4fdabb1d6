"""Set3's observation grid through the fused fit kernels, on the CPU.

At 10,000 observations a point the kernels' observation axis runs, at the
TPU's block sizes, as a grid far longer than any Set1 shape's (1,000
observations), its last block partly masked. ``moments_edges_stats`` and ``fit_error_counts`` run here in
interpret mode with the TPU's block sizes forced, on points of the
``set3`` configuration's cube (``bench/cube.py``), and are held to the
program's reference backend (``repro.core.fitting``, float32 jnp, one pass
over all observations) and to the benchmark's float64 reference
(``bench/reference.py``).

Tolerances, each about ten times the widest gap seen over three seeds and
both redundancies:

* mean and std, relative to the point's std: 1e-5 (seen <= 8e-7). Both
  sides sum 10,000 float32 values, in a different order;
* skew 5e-5 and excess kurtosis 2e-4, absolute (seen <= 5e-6 and 2e-5):
  the third and fourth powers carry the float32 rounding of the centred
  values further;
* the Eq.-5 error of every type, absolute: 2e-5 (seen <= 3e-6), for the
  CDF masses at edges that differ in their last float32 bits;
* the parameters of every type, relative: 1e-5 (seen <= 6e-7), since they
  follow from the moments;
* min, max and the type of least error: equal.
"""

import json
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import distributions as dists
from repro.core import fitting
from repro.kernels.fitpdf import ops as fops

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import cube, reference  # noqa: E402

CFG = json.loads((ROOT / "bench" / "configs" / "set3.json").read_text())
TYPES = tuple(CFG["types"])
BINS = CFG["num_bins"]


def points(redundancy, seed, per_slice=12):
    gen = cube.CubeGenerator(cube.CubeParams(
        cube.Geometry(CFG["source_num_slices"], CFG["lines_per_slice"],
                      CFG["points_per_line"]), CFG["observations"], seed, redundancy))
    rng = np.random.default_rng(seed)
    return np.concatenate([
        gen.points(s, rng.integers(0, CFG["lines_per_slice"], per_slice),
                   rng.integers(0, CFG["points_per_line"], per_slice))
        for s in CFG["set1_slices"]])


def fused(x):
    """The executor's fused path at the TPU's block sizes, interpreted."""
    blocks = dict(block_points=fops.TPU_BLOCK_POINTS, block_obs=fops.TPU_BLOCK_OBS,
                  interpret=True)
    v = jnp.asarray(x)
    m, edges = fops.moments_and_edges(v, BINS, **blocks)
    params_all = dists.fit_all(TYPES, m)
    errs = fops.fit_errors(v, m, params_all, TYPES, BINS, edges=edges, **blocks)
    return m, params_all, errs, fitting.select_best(params_all, errs)


def gap(a, b, scale=1.0):
    return float(np.max(np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))
                        / scale))


@pytest.mark.parametrize("redundancy", ["dup", "nodup"])
@pytest.mark.parametrize("seed", [7, 2**31 + 5])
def test_fused_kernels_at_set3_match_both_references(redundancy, seed):
    x = points(redundancy, seed)
    assert x.shape == (48, 10_000)
    m, params_all, errs, best = fused(x)

    # the program's reference backend, float32
    m_ref = dists.moments_from_values(jnp.asarray(x))
    r_ref = fitting.get_fit_backend("reference", BINS).fit_all(
        jnp.asarray(x), m_ref, TYPES, BINS)
    std = np.asarray(m_ref.std, np.float64)
    assert gap(m.mean, m_ref.mean, std) < 1e-5
    assert gap(m.std, m_ref.std, std) < 1e-5
    assert gap(m.skew, m_ref.skew) < 5e-5
    assert gap(m.kurt, m_ref.kurt) < 2e-4
    np.testing.assert_array_equal(np.asarray(m.vmin), np.asarray(m_ref.vmin))
    np.testing.assert_array_equal(np.asarray(m.vmax), np.asarray(m_ref.vmax))
    np.testing.assert_array_equal(np.asarray(best.type_idx), np.asarray(r_ref.type_idx))
    assert gap(best.error, r_ref.error) < 2e-5

    # the benchmark's float64 reference
    ref = reference.compute(x, TYPES, BINS)
    assert gap(m.mean, ref["mean"], ref["std"]) < 1e-5
    assert gap(m.std, ref["std"], ref["std"]) < 1e-5
    assert gap(m.skew, ref["skew"]) < 5e-5
    assert gap(m.kurt, ref["kurt"]) < 2e-4
    assert gap(errs, ref["errors"]) < 2e-5
    p_ref = ref["params"]
    rel = np.where(p_ref == 0, 0.0, np.abs(np.asarray(params_all, np.float64) - p_ref)
                   / np.where(p_ref == 0, 1.0, np.abs(p_ref)))
    assert float(rel.max()) < 1e-5
    np.testing.assert_array_equal(np.asarray(best.type_idx), ref["best"])
