"""Readings that the check's limits are set from, on the chip.

    python bench/calibrate.py --workload <cell> --seeds 1 2 3 ... [--out FILE]

For each seed, in one process: the cell's set-up (cube, tree, warm-up),
one ``PDFSession.run`` call over the whole cube through the timed path, and
the check's numbers twice over the same sampled points: for the program's
persisted answers, and for the control (the reference on bfloat16-rounded
observations put in the program's place). Prints one JSON line per seed;
the benchmark's own runs never run this.
"""

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
here = Path(__file__).resolve().parent
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != here]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
RUN_DIR = ROOT / "bench" / ".run"
CACHE_DIR = RUN_DIR / "jax_cache"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out", type=Path, default=None)
    args = ap.parse_args(argv)
    import os

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    from bench import harness
    from repro.runtime import cluster

    cell = harness.load_cell(args.workload, ROOT)
    dev = harness.device_info(cell.chips)
    cluster.enable_compilation_cache(CACHE_DIR)
    run_dir = RUN_DIR / f"{cell.name}.calibrate"
    fit_all = cell.traffic["method"] == "baseline"
    out = open(args.out, "a") if args.out else None
    try:
        for seed in args.seeds:
            t = time.perf_counter()
            st = harness.setup(cell, seed, run_dir, CACHE_DIR,
                               log=lambda s: print(s, file=sys.stderr, flush=True))
            setup_s = time.perf_counter() - t
            win = harness.measure(cell, st, 1e-3, run_dir, CACHE_DIR)
            prog = harness.run_check(cell, st, win, seed, fit_all)
            ctl = harness.run_check(cell, st, win, seed, fit_all, control=True)
            line = json.dumps({
                "cell": cell.name, "seed": seed, "device": dev,
                "windows": len(win.handed_back), "call_s": win.t_end - win.t0,
                "compiles": win.compile_delta["compiles"], "setup_s": setup_s,
                "setup": st.parts, "program": prog, "control": ctl})
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
            harness.cleanup(run_dir)
    finally:
        harness.cleanup(run_dir)
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
