"""The benchmark's own copy of the seismic cube generator.

Structure copied from the program's ``data/simulation.py`` (the HPC4e-style
Monte-Carlo cube of arXiv:1805.03141 §6.1), so that no later change to the
program can move the yardstick: a point's observations are the dominant
layer's Vp draws through a smooth per-cell gain, rounded to 3 decimals and
stored as float32. Layer types cycle normal, lognormal, exponential,
uniform every four layers, and slice ``s`` is dominated by layer
``s % num_layers``.

``redundancy`` is the one knob the traffic files set:

* ``dup``: the generator's own cells of 4 points x 2 lines share one gain,
  so a window holds about one distinct (mean, std) per 8 points;
* ``nodup``: cells of 1 point x 1 line, so every point is its own group.

Everything here is host NumPy, deterministic in (seed, slice, line,
point) and independent of how points are batched, so the plain reference
(``bench/reference.py``) regenerates any sampled point bit for bit.
"""

from __future__ import annotations

import os
import shutil
from concurrent import futures
from dataclasses import dataclass
from pathlib import Path

import numpy as np

LAYER_TYPE_CYCLE = ("normal", "lognormal", "exponential", "uniform")
REDUNDANCY = {"dup": (4, 2), "nodup": (1, 1)}  # (points, lines) per cell


def rng_seed(seed: int) -> int:
    """``--seed`` is any whole number; NumPy's generator takes non-negative
    ones, so a negative seed is mapped into the unsigned 64-bit range."""
    return seed if seed >= 0 else seed % (1 << 64)


@dataclass(frozen=True)
class Geometry:
    num_slices: int
    lines_per_slice: int
    points_per_line: int


@dataclass(frozen=True)
class CubeParams:
    geometry: Geometry
    observations: int
    seed: int
    redundancy: str = "dup"
    num_layers: int = 16
    base_vp: float = 3000.0
    quantize_decimals: int = 3

    def __post_init__(self):
        if self.redundancy not in REDUNDANCY:
            raise ValueError(f"redundancy must be one of {sorted(REDUNDANCY)}, "
                             f"got {self.redundancy!r}")


class CubeGenerator:
    """Window- and point-addressable observation generator."""

    def __init__(self, params: CubeParams):
        self.params = params
        self.group_block, self.line_block = REDUNDANCY[params.redundancy]
        rng = np.random.default_rng(rng_seed(params.seed))
        n = params.observations
        draws = []
        for layer in range(params.num_layers):
            kind = LAYER_TYPE_CYCLE[layer % 4]
            scale = params.base_vp * (1.0 + 0.1 * layer)
            if kind == "normal":
                draws.append(rng.normal(scale, 0.3 * scale, size=n))
            elif kind == "lognormal":
                draws.append(np.exp(rng.normal(np.log(scale), 0.5, size=n)))
            elif kind == "exponential":
                draws.append(rng.exponential(scale, size=n))
            else:
                draws.append(rng.uniform(0.5 * scale, 1.5 * scale, size=n))
        self._vp = np.asarray(draws, dtype=np.float64)  # (layers, n)

    def points(self, slice_i: int, lines: np.ndarray, pts: np.ndarray) -> np.ndarray:
        """(k,) line and point indices of one slice -> (k, n) float32."""
        vp = self._vp[slice_i % self.params.num_layers]
        line_idx = np.asarray(lines) // self.line_block
        cell = np.asarray(pts) // self.group_block
        phase = (0.7 * np.sin(0.05 * line_idx + 0.11 * cell)
                 + 0.3 * np.cos(0.02 * line_idx * cell / (1.0 + cell)))
        gain = 1.0 + 0.05 * phase
        obs = np.round(gain[:, None] * vp[None, :], self.params.quantize_decimals)
        return obs.astype(np.float32)

    def window(self, slice_i: int, line_start: int, line_end: int) -> np.ndarray:
        """Lines [line_start, line_end) of one slice -> (lines*ppl, n) float32."""
        ppl = self.params.geometry.points_per_line
        lines = np.repeat(np.arange(line_start, line_end), ppl)
        pts = np.tile(np.arange(ppl), line_end - line_start)
        return self.points(slice_i, lines, pts)


class Cancelled(RuntimeError):
    """Generation stopped through its ``cancel`` event."""


class SliceSource:
    """In-memory window source over whole generated slices: the program's
    ``geometry`` + ``load_window`` protocol. Cube slice ``i`` holds the
    generator's slice ``slices[i]``."""

    def __init__(self, geometry, values: list[np.ndarray], cancel=None):
        self.geometry = geometry
        self._values = values  # per slice (lines, ppl, n) float32
        self._cancel = cancel

    def load_window(self, w) -> np.ndarray:
        if self._cancel is not None and self._cancel.is_set():
            raise Cancelled("cube generation was cancelled")
        block = self._values[w.slice_i][w.line_start:w.line_end]
        return block.reshape(-1, block.shape[-1])


def generate_slices(gen: CubeGenerator, slices, threads: int = 8,
                    chunk_lines: int = 16, cancel=None) -> list[np.ndarray]:
    """Whole slices, generated in line chunks on a few threads (NumPy's
    ufuncs release the interpreter lock). ``cancel`` (an Event) stops it
    between chunks."""
    g = gen.params.geometry
    out = [np.empty((g.lines_per_slice, g.points_per_line, gen.params.observations),
                    np.float32) for _ in slices]

    def fill(k, s, lo):
        if cancel is not None and cancel.is_set():
            raise Cancelled("cube generation was cancelled")
        hi = min(lo + chunk_lines, g.lines_per_slice)
        out[k][lo:hi] = gen.window(s, lo, hi).reshape(hi - lo, g.points_per_line, -1)

    with futures.ThreadPoolExecutor(threads) as pool:
        jobs = [pool.submit(fill, k, s, lo) for k, s in enumerate(slices)
                for lo in range(0, g.lines_per_slice, chunk_lines)]
        for j in jobs:
            j.result()
    return out


def export(source, cube_dir: Path):
    """Write ``source`` to ``cube_dir`` with the program's own exporter and
    flush it to disk, so no write-back of the cube runs inside the measured
    window. Returns the program's ``SourceSpec(kind='file')``."""
    from repro.data.file_source import export_cube

    if cube_dir.exists():
        shutil.rmtree(cube_dir)
    spec = export_cube(source, cube_dir)
    os.sync()
    return spec
