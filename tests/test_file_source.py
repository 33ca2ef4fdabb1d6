"""File-backed cube sources (data/file_source.py): export/read round-trip,
manifest content hashing, spec integration (kind='file'), and full-pipeline
bitwise fidelity vs the simulation the cube was exported from."""

import dataclasses
import json
import shutil

import numpy as np
import pytest

from repro.api import (
    ComputeSpec,
    ExecSpec,
    MethodSpec,
    PDFSession,
    PipelineSpec,
    SourceSpec,
    build_source,
    source_spec_for,
)
from repro.core.regions import Window
from repro.data.file_source import (
    FileCubeSource,
    LAYOUTS,
    export_cube,
    manifest_sha,
    read_manifest,
)
from repro.data.loader import ThrottledSource

from repro.core.executor import RESULT_FIELDS

SIM_SOURCE = SourceSpec(num_slices=4, lines_per_slice=9, points_per_line=11,
                        observations=120)


@pytest.fixture(scope="module")
def cube(tmp_path_factory):
    """One exported cube shared by the module: (sim spec, file spec, dir)."""
    d = tmp_path_factory.mktemp("cube")
    file_spec = export_cube(SIM_SOURCE, d, lines_per_chunk=4)
    return SIM_SOURCE, file_spec, d


def test_layouts_mirror_spec_constant():
    from repro.api.spec import FILE_LAYOUTS

    assert FILE_LAYOUTS == LAYOUTS


def test_export_returns_runnable_file_spec(cube):
    _, file_spec, d = cube
    assert file_spec.kind == "file" and file_spec.path == str(d)
    # advisory geometry filled from the actual cube
    assert file_spec.num_slices == 4 and file_spec.lines_per_slice == 9
    assert file_spec.points_per_line == 11 and file_spec.observations == 120
    src = build_source(file_spec)
    assert isinstance(src, FileCubeSource)
    assert src.geometry.num_slices == 4


def test_window_reads_match_simulation_bitwise(cube):
    sim_spec, file_spec, _ = cube
    sim = build_source(sim_spec)
    src = build_source(file_spec)
    # windows inside one chunk, spanning the chunk boundary at line 4,
    # spanning two boundaries, and the ragged tail chunk (lines 8..9)
    for w in (Window(0, 0, 3), Window(1, 2, 6), Window(2, 0, 9),
              Window(3, 7, 9), Window(3, 8, 9)):
        got = src.load_window(w)
        want = sim.load_window(w)
        assert got.dtype == np.float32
        np.testing.assert_array_equal(got, want)


def test_window_bounds_validated(cube):
    _, file_spec, _ = cube
    src = build_source(file_spec)
    with pytest.raises(ValueError, match="outside cube"):
        src.load_window(Window(4, 0, 3))
    with pytest.raises(ValueError, match="outside cube"):
        src.load_window(Window(0, 5, 12))


def test_manifest_sha_is_location_independent(cube, tmp_path):
    _, file_spec, d = cube
    moved = tmp_path / "moved"
    shutil.copytree(d, moved)
    assert manifest_sha(moved) == manifest_sha(d)
    spec_a = PipelineSpec(source=file_spec)
    spec_b = PipelineSpec(source=dataclasses.replace(file_spec,
                                                     path=str(moved)))
    assert spec_a.content_hash() == spec_b.content_hash()


def test_different_data_different_manifest_sha(cube, tmp_path):
    _, _, d = cube
    other = export_cube(dataclasses.replace(SIM_SOURCE, seed=1),
                        tmp_path / "other", lines_per_chunk=4)
    assert manifest_sha(other.path) != manifest_sha(d)


def test_advisory_fields_do_not_change_file_hash(cube):
    _, file_spec, _ = cube
    a = PipelineSpec(source=file_spec)
    b = PipelineSpec(source=dataclasses.replace(file_spec, seed=99,
                                                observations=7))
    assert a.content_hash() == b.content_hash()


def test_hand_edited_manifest_cannot_keep_its_sha(cube, tmp_path):
    _, _, d = cube
    tampered = tmp_path / "tampered"
    shutil.copytree(d, tampered)
    m = json.loads((tampered / "manifest.json").read_text())
    m["chunks"][0]["sha256"] = "0" * 64  # forged chunk hash, stored sha kept
    (tampered / "manifest.json").write_text(json.dumps(m))
    assert manifest_sha(tampered) != manifest_sha(d)


def test_verify_catches_corrupt_chunk(cube, tmp_path):
    _, _, d = cube
    bad = tmp_path / "bad"
    shutil.copytree(d, bad)
    name = read_manifest(bad)["chunks"][0]["file"]
    arr = np.load(bad / name)
    arr = arr.copy()
    arr.flat[0] += 1.0
    np.save(bad / name, arr)
    FileCubeSource(d).verify()  # pristine cube passes
    with pytest.raises(ValueError, match="corrupt"):
        FileCubeSource(bad).verify()


def test_manifest_with_coverage_gap_rejected(cube, tmp_path):
    """A manifest whose chunks don't tile a slice must be refused up front
    — load_window would otherwise return uninitialized buffer rows for the
    uncovered lines."""
    _, _, d = cube
    gappy = tmp_path / "gappy"
    shutil.copytree(d, gappy)
    m = json.loads((gappy / "manifest.json").read_text())
    dropped = [c for c in m["chunks"]
               if not (c["slice"] == 1 and c["line_start"] == 4)]
    assert len(dropped) == len(m["chunks"]) - 1
    m["chunks"] = dropped
    (gappy / "manifest.json").write_text(json.dumps(m))
    with pytest.raises(ValueError, match="does not cover slice 1"):
        FileCubeSource(gappy)


def test_missing_manifest_is_a_clear_error(tmp_path):
    with pytest.raises(ValueError, match="export_cube"):
        FileCubeSource(tmp_path)
    spec = PipelineSpec(source=SourceSpec(kind="file", path=str(tmp_path)))
    with pytest.raises(ValueError, match="export_cube"):
        spec.content_hash()


def test_throttled_file_source(cube):
    _, file_spec, _ = cube
    throttled = dataclasses.replace(file_spec, throttle_mb_s=1000.0)
    src = build_source(throttled)
    assert isinstance(src, ThrottledSource)
    assert isinstance(src.inner, FileCubeSource)
    # the throttle is an execution-time model, not a data identity change
    assert (PipelineSpec(source=throttled).content_hash()
            == PipelineSpec(source=file_spec).content_hash())
    # source_spec_for round-trips the wrapped reader, advisory geometry
    # filled from the manifest (like export_cube's returned spec)
    back = source_spec_for(src)
    assert back.kind == "file" and back.path == file_spec.path
    assert back.throttle_mb_s == pytest.approx(1000.0)
    assert (back.num_slices, back.lines_per_slice, back.points_per_line,
            back.observations) == (4, 9, 11, 120)


def test_file_spec_json_roundtrip(cube):
    _, file_spec, _ = cube
    spec = PipelineSpec(source=file_spec,
                        method=MethodSpec(name="grouping"),
                        compute=ComputeSpec(window_lines=3, num_bins=20))
    back = PipelineSpec.from_json(spec.to_json())
    assert back == spec
    assert back.content_hash() == spec.content_hash()


def test_build_source_external_error_points_at_file_path():
    with pytest.raises(ValueError, match="export_cube"):
        build_source(SourceSpec(kind="external"))


@pytest.mark.parametrize("build", [
    lambda: SourceSpec(kind="file"),  # path required
    lambda: SourceSpec(path="/somewhere"),  # path only for kind='file'
    lambda: SourceSpec(kind="external", path="/somewhere"),
    lambda: SourceSpec(kind="file", path="/somewhere", layout="columnar"),
])
def test_invalid_file_specs_rejected(build):
    with pytest.raises(ValueError):
        build()


def test_pipeline_results_bitwise_identical_to_simulation(cube):
    """The acceptance round-trip: export_cube(sim_spec) then running the
    same pipeline with kind='file' yields bitwise-identical SliceResults."""
    sim_spec, file_spec, _ = cube
    knobs = dict(method=MethodSpec(name="grouping"),
                 compute=ComputeSpec(window_lines=4, num_bins=20))
    r_sim = PDFSession(PipelineSpec(source=sim_spec, **knobs)).run_all([2])[2]
    r_file = PDFSession(PipelineSpec(source=file_spec, **knobs)).run_all([2])[2]
    for f in RESULT_FIELDS:
        np.testing.assert_array_equal(getattr(r_sim, f), getattr(r_file, f),
                                      err_msg=f)
    assert r_sim.avg_error == r_file.avg_error
    # the two runs are distinct computations provenance-wise: one is
    # identified by generator knobs, the other by the bytes on disk
    assert r_sim.spec_hash != r_file.spec_hash


def test_prefetched_file_run_matches_serial(cube):
    _, file_spec, _ = cube
    base = PipelineSpec(source=file_spec, compute=ComputeSpec(window_lines=3))
    serial = dataclasses.replace(
        base, execution=ExecSpec(prefetch=False, async_persist=False))
    r_pre = PDFSession(base).run_all([1])[1]
    r_ser = PDFSession(serial).run_all([1])[1]
    for f in RESULT_FIELDS:
        np.testing.assert_array_equal(getattr(r_pre, f), getattr(r_ser, f))


# -- overwrite guard / versioned manifests (streaming, DESIGN.md §16) ----------


def test_export_refuses_to_clobber_live_cube(tmp_path):
    """Re-exporting over an existing cube would silently re-key every spec
    hash derived from it: refused unless overwrite=True, and the refusal
    happens before ANY chunk is written — the old cube survives untouched."""
    d = tmp_path / "cube"
    export_cube(SIM_SOURCE, d, lines_per_chunk=4)
    before_manifest = (d / "manifest.json").read_bytes()
    before_files = sorted(p.name for p in d.iterdir())

    other = dataclasses.replace(SIM_SOURCE, seed=99)
    with pytest.raises(FileExistsError, match="overwrite=True"):
        export_cube(other, d, lines_per_chunk=4)
    # nothing changed: same file set, manifest byte-identical
    assert sorted(p.name for p in d.iterdir()) == before_files
    assert (d / "manifest.json").read_bytes() == before_manifest

    # explicit overwrite replaces the cube (and re-keys its sha)
    old_sha = manifest_sha(d)
    export_cube(other, d, lines_per_chunk=4, overwrite=True)
    assert manifest_sha(d) != old_sha


def test_export_into_manifestless_dir_is_allowed(tmp_path):
    """A directory without a manifest (a crashed export's leftovers, or
    just a plain dir) is not a cube — no guard, export proceeds."""
    d = tmp_path / "cube"
    d.mkdir()
    (d / "stray.txt").write_text("not a cube")
    spec = export_cube(SIM_SOURCE, d, lines_per_chunk=4)
    assert build_source(spec).geometry.num_slices == 4


def test_versioned_manifest_reads(tmp_path):
    from repro.data.file_source import manifest_version
    from repro.streaming import append_realizations

    d = tmp_path / "cube"
    export_cube(SIM_SOURCE, d, lines_per_chunk=4)
    assert manifest_version(d) == 1
    sha1 = manifest_sha(d)
    block = np.zeros((SIM_SOURCE.lines_per_slice, SIM_SOURCE.points_per_line,
                      3), np.float32)
    assert append_realizations(d, {0: block}) == 2
    assert manifest_version(d) == 2
    # version pinning: the archived manifest is still addressable, and its
    # sha is exactly what the live manifest hashed to before the append
    assert manifest_sha(d, version=1) == sha1
    assert manifest_sha(d) != sha1
    assert read_manifest(d, version=1).get("version", 1) == 1
    assert read_manifest(d, version=2)["version"] == 2
    with pytest.raises(ValueError, match="no version 7"):
        read_manifest(d, version=7)


@pytest.fixture(scope="module")
def appended(tmp_path_factory):
    """A format-2 cube: SIM_SOURCE with 5 observations appended to slice 0
    (chunks of 4 lines, so slice 0 has a base and a delta layer)."""
    from repro.streaming import append_realizations

    d = tmp_path_factory.mktemp("appended")
    export_cube(SIM_SOURCE, d, lines_per_chunk=4)
    rng = np.random.default_rng(3)
    block = rng.standard_normal((SIM_SOURCE.lines_per_slice,
                                 SIM_SOURCE.points_per_line, 5), np.float32)
    append_realizations(d, {0: block})
    return d, block


def _strided(src, w, lo, hi):
    """The window's observations [lo, hi) through the memmap path: each
    half is an observation sub-range, which no chunk row matches."""
    mid = (lo + hi) // 2
    return np.concatenate([src.load_window_obs(w, lo, mid),
                           src.load_window_obs(w, mid, hi)], axis=1)


BASE = SIM_SOURCE.observations
POSITIONAL_CASES = {
    # name: (cube, window, obs range or None for load_window, positional?)
    "straddles_chunk_boundary": ("cube", Window(1, 2, 7), None, True),
    "short_last_window": ("cube", Window(2, 8, 9), None, True),
    "inside_one_chunk": ("cube", Window(3, 4, 7), None, True),
    "format2_untouched_slice_full_range": ("appended", Window(1, 1, 6), None, True),
    "format2_delta_layer": ("appended", Window(0, 2, 7), (BASE, BASE + 5), True),
    "format2_appended_slice_full_range": ("appended", Window(0, 2, 7), None, False),
    "format2_obs_sub_range": ("appended", Window(0, 0, 9), (3, BASE + 2), False),
    "out_wrong_shape": ("cube", Window(1, 2, 7), None, "shape"),
    "out_wrong_dtype": ("cube", Window(1, 2, 7), None, "dtype"),
}


@pytest.mark.parametrize("case", sorted(POSITIONAL_CASES))
def test_positional_reads_match_memmap_path(cube, appended, case):
    """Where the requested observations are a chunk's whole row width, the
    window is read by positional reads into ``out`` and no chunk memmap is
    opened; else the strided memmap path runs. Both give the same bytes,
    and ``out`` of the wrong shape or dtype is refused."""
    which, w, obs, positional = POSITIONAL_CASES[case]
    d = cube[2] if which == "cube" else appended[0]
    src = FileCubeSource(d)
    lo, hi = obs or (0, src.slice_observations(w.slice_i))
    shape = (w.num_lines * SIM_SOURCE.points_per_line, hi - lo)
    if positional in ("shape", "dtype"):
        bad = (np.empty((shape[0] - 1, shape[1]), np.float32)
               if positional == "shape" else np.empty(shape, np.float64))
        with pytest.raises(ValueError, match="out must be"):
            src.load_window(w, out=bad)
        return
    out = np.full(shape, np.nan, np.float32)
    got = (src.load_window(w, out=out) if obs is None
           else src.load_window_obs(w, lo, hi, out=out))
    assert got is out
    assert (not src._mmaps) == positional  # no memmap left open
    want = _strided(FileCubeSource(d), w, lo, hi)
    np.testing.assert_array_equal(got, want)
    if which == "appended" and w.slice_i == 0:
        delta = appended[1][w.line_start:w.line_end].reshape(shape[0], -1)
        full = np.concatenate([FileCubeSource(d).load_window_obs(w, 0, BASE),
                               delta], axis=1)
        np.testing.assert_array_equal(got, full[:, lo:hi])
    # and without ``out``: a fresh array with the same bytes
    fresh = (FileCubeSource(d).load_window(w) if obs is None
             else FileCubeSource(d).load_window_obs(w, lo, hi))
    np.testing.assert_array_equal(fresh, want)


def test_concurrent_positional_reads_with_evictions(cube, monkeypatch):
    """More reader threads than cores, a descriptor LRU of 2 so chunk files
    are evicted while other threads still read them, and a short switch
    interval: every read still returns the exported bytes."""
    import sys
    import threading

    from repro.data import file_source

    sim_spec, _, d = cube
    sim = build_source(sim_spec)
    monkeypatch.setattr(file_source, "_MMAP_CACHE_SIZE", 2)
    src = FileCubeSource(d)
    windows = [Window(s, lo, min(lo + 3, 9)) for s in range(4) for lo in range(0, 9, 2)]
    want = {w: sim.load_window(w) for w in windows}
    bad, done = [], []

    def reader(k):
        rng = np.random.default_rng(k)
        for i in rng.permutation(len(windows) * 3) % len(windows):
            w = windows[i]
            if not np.array_equal(src.load_window(w), want[w]):
                bad.append(w)
        done.append(k)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=reader, args=(k,)) for k in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert sorted(done) == list(range(16)) and bad == []
    assert len(src._files) <= 2
