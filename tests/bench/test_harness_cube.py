"""bench/cube.py: the benchmark's copy of the generator."""

import numpy as np
import pytest

from bench import cube

from repro.core import distributions as dists
from repro.core import grouping as grp
from repro.core.regions import CubeGeometry, iter_windows
from repro.data.simulation import SeismicSimulation, SimulationConfig

GEOM = (8, 10, 12)


def params(seed, redundancy="dup", obs=64):
    return cube.CubeParams(cube.Geometry(*GEOM), obs, seed, redundancy)


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5])
def test_dup_equals_the_program_generator(seed):
    sim = SeismicSimulation(SimulationConfig(
        geometry=CubeGeometry(*GEOM), num_simulations=64, seed=seed))
    gen = cube.CubeGenerator(params(seed))
    for s in range(GEOM[0]):
        for w in iter_windows(sim.geometry, s, 3):
            np.testing.assert_array_equal(
                gen.window(s, w.line_start, w.line_end), sim.load_window(w))


def test_points_and_slices_do_not_depend_on_batching():
    gen = cube.CubeGenerator(params(3, "nodup"))
    whole = cube.generate_slices(gen, [5, 2], threads=3, chunk_lines=4)
    src = cube.SliceSource(CubeGeometry(2, GEOM[1], GEOM[2]), whole)
    for k, s in enumerate((5, 2)):
        np.testing.assert_array_equal(
            src.load_window(next(iter_windows(src.geometry, k, GEOM[1]))),
            gen.window(s, 0, GEOM[1]))
    lines, pts = np.array([9, 0, 4]), np.array([11, 3, 0])
    np.testing.assert_array_equal(gen.points(2, lines, pts), whole[1][lines, pts])


def groups(values):
    m = dists.moments_from_values(values)
    return grp.group_host(grp.quantize_keys_host(
        np.asarray(m.mean), np.asarray(m.var))).num_groups


@pytest.mark.parametrize("slice_i", [200, 201, 202, 203])
def test_nodup_gives_one_group_per_point(slice_i):
    p = cube.CubeParams(cube.Geometry(501, 6, 40), 1000, 11, "nodup")
    values = cube.CubeGenerator(p).window(slice_i, 0, 6)
    assert groups(values) == values.shape[0] == 240
    dup = cube.CubeGenerator(cube.CubeParams(p.geometry, 1000, 11, "dup"))
    assert groups(dup.window(slice_i, 0, 6)) == 240 // 8


def test_negative_and_large_seeds_are_deterministic():
    a = cube.CubeGenerator(params(-3)).window(1, 0, 2)
    b = cube.CubeGenerator(params(-3)).window(1, 0, 2)
    c = cube.CubeGenerator(params(3)).window(1, 0, 2)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
