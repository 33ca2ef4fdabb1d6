"""The distributed correctness harness (DESIGN.md §17): real multi-process
cluster runs, verified bitwise against a serial reference.

Each test spawns ``launch/run_pdf`` worker subprocesses — one python process
per cluster seat, each seeing exactly 1 CPU device — sharing one
``jax.distributed`` coordinator and one ``--out-dir``, then asserts the
persisted window arrays are bitwise-identical to the single-process run
(``runtime.cluster.verify_outputs``). The cold-start tests drive the
persistent compilation cache the same way: only a subprocess relaunch
observes real cold-start cost (in-process, the executor's jitted-fn cache
would make the assertion vacuous).

Tests that need a ``jax.distributed`` world skip cleanly when the platform
cannot run a coordinator (sandboxes without localhost gRPC)."""

import os
import re
import socket
import subprocess
import sys
from pathlib import Path

import pytest

from repro.runtime.cluster import verify_outputs

REPO = Path(__file__).resolve().parent.parent

# The shared seismic spec every cluster test runs: 4 slices so a 4-process
# run still deals one slice per seat, small enough that a worker's life is
# dominated by startup, not compute.
SPEC_FLAGS = [
    "--num-slices", "4", "--lines", "6", "--ppl", "10", "--obs", "80",
    "--method", "grouping", "--window-lines", "3", "--num-bins", "20",
    "--slices", "0", "1", "2", "3",
]

# stderr fingerprints of "this platform cannot run a distributed
# coordinator" — anything else is a real failure and must fail the test
_COORD_FAIL = ("DEADLINE_EXCEEDED", "UNAVAILABLE", "failed to connect",
               "Barrier timed out", "coordination service")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _worker_env() -> dict:
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_NUM_CPU_DEVICES"] = "1"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    return env


def _run_serial(out_dir, extra=(), env=None) -> str:
    p = subprocess.run(
        [sys.executable, "-m", "repro.launch.run_pdf", *SPEC_FLAGS,
         "--out-dir", str(out_dir), *extra],
        env={**_worker_env(), **(env or {})}, capture_output=True, text=True,
        timeout=600)
    assert p.returncode == 0, f"stdout:\n{p.stdout}\nstderr:\n{p.stderr}"
    return p.stdout + p.stderr


def _run_cluster(nprocs, out_dir, extra=()) -> list[str]:
    """Spawn one run_pdf worker per seat against a shared out_dir; returns
    each worker's combined output. Skips the calling test when the failure
    is the platform refusing the coordinator, fails it otherwise."""
    coord = f"127.0.0.1:{_free_port()}"
    procs = [
        subprocess.Popen(
            [sys.executable, "-m", "repro.launch.run_pdf", *SPEC_FLAGS,
             "--out-dir", str(out_dir),
             "--num-processes", str(nprocs), "--process-id", str(i),
             "--coordinator", coord, *extra],
            env=_worker_env(), stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
        for i in range(nprocs)
    ]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append((p.returncode, out))
    for rc, out in outs:
        if rc != 0:
            if nprocs > 1 and any(m in out for m in _COORD_FAIL):
                pytest.skip("platform cannot run a jax.distributed "
                            "coordinator here")
            raise AssertionError(f"worker failed (rc={rc}):\n{out}")
    return [out for _, out in outs]


@pytest.fixture(scope="module")
def serial_ref(tmp_path_factory):
    """The single-process reference out_dir every cluster run is compared
    against (plus its shared compile cache, so later launches skip XLA)."""
    base = tmp_path_factory.mktemp("serial")
    out, cache = base / "out", base / "compile-cache"
    log = _run_serial(out, ["--compile-cache-dir", str(cache)])
    assert "[total]" in log
    return out, cache


@pytest.mark.parametrize("nprocs", [1, 2, 4])
def test_cluster_matches_serial_reference(nprocs, serial_ref, tmp_path):
    """The acceptance invariant: N worker processes sharing one out_dir
    persist exactly the windows the serial run does, bitwise."""
    ref, cache = serial_ref
    out = tmp_path / f"out{nprocs}"
    logs = _run_cluster(nprocs, out, ["--compile-cache-dir", str(cache)])
    if nprocs > 1:
        assert any("[cluster] jax.distributed process" in l for l in logs)
    windows, arrays = verify_outputs(ref, out)
    assert windows == 8  # 4 slices x 2 windows (6 lines / 3 per window)
    assert arrays > 0


def test_worker_requires_seat_and_out_dir():
    """Placement misuse fails loudly at spec time: multi-process without a
    process id, and without a shared out_dir, both refuse to launch."""
    p = subprocess.run(
        [sys.executable, "-m", "repro.launch.run_pdf", *SPEC_FLAGS,
         "--num-processes", "2", "--out-dir", "/tmp/unused-seatless"],
        env=_worker_env(), capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "process_id" in p.stderr or "process-id" in p.stderr
    p = subprocess.run(
        [sys.executable, "-m", "repro.launch.run_pdf", *SPEC_FLAGS,
         "--num-processes", "2", "--process-id", "0"],
        env=_worker_env(), capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "out_dir" in p.stderr or "out-dir" in p.stderr


# -- cold-start elimination (the persistent compilation cache) ------------------


def _new_compilations(log: str) -> int:
    m = re.search(r"new_compilations=(\d+)", log)
    assert m, f"no [compile] line in:\n{log}"
    return int(m.group(1))


@pytest.fixture(scope="module")
def warm_cache(tmp_path_factory):
    """Two identical launches sharing one --compile-cache-dir; returns the
    cache dir and both logs for the cold-start assertions."""
    base = tmp_path_factory.mktemp("coldstart")
    cache = base / "compile-cache"
    log1 = _run_serial(base / "run1", ["--compile-cache-dir", str(cache)])
    log2 = _run_serial(base / "run2", ["--compile-cache-dir", str(cache)])
    return base, cache, log1, log2


def test_second_launch_reports_zero_new_compilations(warm_cache):
    """The cold-start acceptance criterion: a relaunched identical spec
    serves every executable from the persistent cache — the [compile] line
    reports zero new compilations (= zero persistent-cache misses; backend
    compile *calls* still fire on hits, which is why the indicator is the
    miss count)."""
    base, cache, log1, log2 = warm_cache
    assert _new_compilations(log1) > 0  # the first launch really compiled
    assert _new_compilations(log2) == 0
    assert re.search(r"cache_hits=[1-9]", log2)
    # the entries sit in the given directory itself (XLA keys each by the
    # program), with no per-spec subdirectory
    assert any(f.is_file() for f in cache.iterdir())
    assert not any(f.is_dir() for f in cache.iterdir())
    assert f"dir={cache}" in log2
    # and the warm run's persisted windows are the cold run's, bitwise
    verify_outputs(base / "run1", base / "run2")


def test_corrupt_cache_entry_is_warned_miss_not_crash(warm_cache):
    """Cache-dir corruption degrades, never aborts: garbage bytes in every
    cache entry turn the next launch's hits into warned misses — JAX
    recompiles and the run completes with intact results."""
    base, cache, _, _ = warm_cache
    corrupted = 0
    for f in cache.rglob("*"):
        if f.is_file():
            f.write_bytes(b"not an xla executable")
            corrupted += 1
    assert corrupted > 0
    log3 = _run_serial(base / "run3", ["--compile-cache-dir", str(cache)])
    assert "[total]" in log3  # the run completed
    assert ("compilation cache" in log3 and "rror" in log3) \
        or _new_compilations(log3) > 0, log3
    verify_outputs(base / "run1", base / "run3")


def test_env_cache_dir_wins_over_spec(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set in the environment, the program
    uses exactly that directory: the spec's --compile-cache-dir gets
    nothing, and a relaunch hits the environment's entries."""
    env_dir, spec_dir = tmp_path / "env-cache", tmp_path / "spec-cache"
    extra = ["--compile-cache-dir", str(spec_dir)]
    env = {"JAX_COMPILATION_CACHE_DIR": str(env_dir)}
    log1 = _run_serial(tmp_path / "run1", extra, env=env)
    log2 = _run_serial(tmp_path / "run2", extra, env=env)
    assert f"dir={env_dir}" in log1
    assert _new_compilations(log1) > 0
    assert _new_compilations(log2) == 0
    assert any(f.is_file() for f in env_dir.iterdir())
    assert not spec_dir.exists()
