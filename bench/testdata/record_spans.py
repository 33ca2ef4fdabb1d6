"""Record the small chip trace with the program's spans that
``tests/bench/test_span_trace.py`` reads: two 25-line Set1 windows (6,275
points x 1,000 observations) through one ``PDFSession`` with
``method='grouping'`` inside the benchmark's ``bench.window`` span, on one
TPU chip, the session built inside the window as the benchmark builds it.

    python bench/testdata/record_spans.py --out bench/testdata

Writes ``grouping_2win_spans.xplane.pb`` there and prints the ``pdf.*``
events of each host line, the idle gaps and the untraced idle share.
"""

import argparse
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
here = Path(__file__).resolve().parent
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != here]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

NAME = "grouping_2win_spans.xplane.pb"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", type=Path, required=True)
    args = ap.parse_args()
    import jax

    from bench import cube, harness, span_trace
    from bench import trace as tracemod
    from repro.api import PDFSession

    if jax.devices()[0].platform != "tpu":
        print("record_spans: no TPU", file=sys.stderr)
        return 1
    cell = harness.load_cell("set1.grouping_ml", ROOT)
    cell.config = dict(cell.config, num_slices=1)
    cell.traffic = dict(cell.traffic, method="grouping")
    gen = cube.CubeGenerator(harness.cube_params(cell, 5))
    values = gen.window(201, 0, 50).reshape(50, 251, -1)
    from repro.core.regions import CubeGeometry

    src = cube.SliceSource(CubeGeometry(1, 50, 251), [values])
    tmp = Path(tempfile.mkdtemp())
    try:
        exported = cube.export(src, tmp / "cube")
        spec = harness.pipeline_spec(cell, exported, tmp / "out")
        for _ in PDFSession(spec).run():  # compile and warm up
            pass
        harness.start_trace(tmp / "trace")
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in PDFSession(spec).run():
                pass
        jax.profiler.stop_trace()
        xplane = tracemod.find_xplane(tmp / "trace")
        args.out.mkdir(parents=True, exist_ok=True)
        dst = args.out / NAME
        shutil.copy(xplane, dst)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(dst))
    for plane in pd.planes:
        for k, line in enumerate(plane.lines):
            evs = [e for e in line.events if e.name.startswith("pdf.")]
            if evs:
                print(f"[line] {plane.name} #{k} {line.name!r} pdf_events={len(evs)}")
                for e in evs:
                    print(f"  {e.name} start_ns={e.start_ns} dur_ns={e.duration_ns} "
                          f"stats={dict(e.stats)}")
    tr = tracemod.load(dst)
    print(f"[reduce] window_s={tracemod.window_seconds(tr)} "
          f"busy_s={tracemod.busy_seconds(tr, 1)}")
    print(f"[reduce] idle_gaps={tracemod.idle_gaps(tr, 1)}")
    print(f"[reduce] untraced_idle={span_trace.untraced_idle(tr, 1)}")
    print(f"[size] {dst.stat().st_size}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
