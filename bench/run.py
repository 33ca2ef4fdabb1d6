"""The benchmark's command: one run of one cell.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Prints the set-up parts and the device on standard error, then the numbers
the check compared, each beside its limit, as the last lines there; and as
the last line of standard output one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and with ``--trace 1``
``breakdown``), and last ``check``. With ``--trace 0`` the metrics are the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics, read
from a profiler trace of the whole window. Exits 1 and prints no result
when JAX finds no TPU or fewer chips than the cell asks for.

JAX's persistent compilation cache is kept in ``bench/.run/jax_cache``
inside the checkout, so only the first run of a cell there compiles.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
RUN_DIR = ROOT / "bench" / ".run"
CACHE_DIR = RUN_DIR / "jax_cache"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # bench/ itself must not shadow the standard library (bench/trace.py)
    here = Path(__file__).resolve().parent
    sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != here]
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    from bench import harness

    cell = harness.load_cell(args.workload, ROOT)
    try:
        harness.run_cell(cell, args.seed, args.seconds, bool(args.trace), T_START,
                         RUN_DIR / cell.name, CACHE_DIR)
    except harness.ChipMissing as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
