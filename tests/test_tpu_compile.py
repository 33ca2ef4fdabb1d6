"""Compile the main-path Pallas kernels for a TPU v5e chip at the paper's
window shapes, without a chip.

The TPU compiler is installed with JAX, and it compiles for a described
(not attached) topology. Interpret mode, which every other kernel test
uses on the CPU, cannot show what Mosaic refuses: primitives it does not
lower (``erf``, ``igamma``), blocks off the tiling, or more VMEM than a
kernel may use. These tests run nothing on a device.

Set1 window: 25 lines x 251 points = 6,275 points, padded to a multiple
of the 8-point TPU block (6,280), x 1,000 observations, L = 20 bins. The
Set3 regime has 10,000 observations.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every test worker imports every test file.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import distributions as dists
from repro.kernels.fitpdf.kernel import fit_error_counts, moments_edges_stats
from repro.kernels.hist.kernel import hist_counts

SET1_POINTS = 6280  # 6,275 Set1 window points padded to the 8-point block
SET1_OBS = 1000
SET3_OBS = 10000
NUM_BINS = 20

# Special-function primitives Mosaic has no lowering for; the fused fit
# used to evaluate the CDFs (and so these) inside its epilogue.
_SPECIAL = re.compile(
    r"\b(erf|erfc|erf_inv|igamma|igammac|lgamma|digamma|"
    r"regularized_incomplete_beta|random_gamma_grad)\b")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it out of the cache.
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _assert_mosaic(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n", [SET1_OBS, SET3_OBS], ids=["set1", "set3"])
def test_moments_edges_compiles_for_v5e(one_chip, n):
    lowered = moments_edges_stats.lower(
        _spec((SET1_POINTS, n), one_chip), NUM_BINS, interpret=False)
    _assert_mosaic(lowered.compile())


@pytest.mark.parametrize(
    "n,types",
    [(SET1_OBS, dists.TYPES_4), (SET1_OBS, dists.TYPES_10), (SET3_OBS, dists.TYPES_4)],
    ids=["set1-4types", "set1-10types", "set3-4types"],
)
def test_fit_error_counts_compiles_for_v5e(one_chip, n, types):
    t = len(types)
    args = (
        _spec((SET1_POINTS, n), one_chip),
        _spec((SET1_POINTS,), one_chip),
        _spec((SET1_POINTS,), one_chip),
        _spec((t, SET1_POINTS, NUM_BINS), one_chip),
    )
    kernel = functools.partial(fit_error_counts, num_bins=NUM_BINS, interpret=False)
    _assert_mosaic(jax.jit(kernel).lower(*args).compile())
    # The masses are an input: no special function is traced into the
    # kernel body (the pallas_call's jaxpr prints inside this one).
    assert not _SPECIAL.findall(str(jax.make_jaxpr(kernel)(*args)))


def test_fused_fit_errors_compiles_for_v5e(one_chip):
    """The whole jitted fit step (XLA masses + the Mosaic kernel) with 10
    types: the special functions compile in XLA, outside the kernel."""
    from repro.kernels.fitpdf import ops as fops

    types = dists.TYPES_10

    def step(values, vmin, vmax, mean, var):
        m = dists.Moments(mean, var, jnp.zeros_like(mean), jnp.zeros_like(mean),
                          vmin, vmax)
        params = dists.fit_all(types, m)
        return fops.fit_errors(values, m, params, types, NUM_BINS, interpret=False)

    p = SET1_POINTS
    vec = _spec((p,), one_chip)
    compiled = jax.jit(step).lower(
        _spec((p, SET1_OBS), one_chip), vec, vec, vec, vec).compile()
    _assert_mosaic(compiled)


def test_hist_counts_compiles_for_v5e(one_chip):
    lowered = hist_counts.lower(
        _spec((SET1_POINTS, SET1_OBS), one_chip),
        _spec((SET1_POINTS, 1), one_chip),
        _spec((SET1_POINTS, 1), one_chip),
        NUM_BINS,
        interpret=False,
    )
    _assert_mosaic(lowered.compile())


def test_device_select_refused_on_tpu(one_chip):
    """TPU float64 is emulated, so device grouping keys can land one unit
    away from the host keys: device Select refuses a TPU device up front
    instead of partitioning differently."""
    from repro.core.executor import PDFConfig, StagedExecutor

    cfg = PDFConfig(method="grouping", select_backend="device")
    with pytest.raises(ValueError, match="select_backend='device' is refused on tpu"):
        StagedExecutor(cfg, None, sharding=one_chip)
    StagedExecutor(PDFConfig(method="grouping"), None, sharding=one_chip)
