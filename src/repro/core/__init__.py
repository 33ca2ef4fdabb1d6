# The paper's primary contribution: parallel PDF computation on big spatial
# data — distribution fitting (Algorithm 3/4), Eq.-5 error, grouping (§5.2),
# reuse (§5.2.1), decision-tree ML prediction (§5.3), sampling (§5.4), and
# the windowed pipeline (Algorithms 1-2), run by a staged executor that
# overlaps load / compute / persist (executor.py) — all as fused JAX
# computations.
#
# Submodules and the names below are imported on first use (PEP 562), so
# importing one submodule (``repro.core.regions``, which ``repro.data``
# needs) does not import the executor. An eager package import made the
# package and its submodules a cycle (executor -> repro.core -> executor),
# and two threads importing different ends of it could be handed a partly
# initialized module.
import importlib

_SUBMODULES = ("distributions", "executor", "fitting", "grouping", "ml_predict",
               "pdf_error", "pipeline", "regions", "reuse", "sampling")
_NAMES = {
    "TYPES_4": "distributions", "TYPES_10": "distributions",
    "Moments": "distributions", "moments_from_values": "distributions",
    "FitResult": "fitting", "compute_pdf_and_error": "fitting",
    "compute_pdf_with_predicted_type": "fitting",
    "ExecutorConfig": "executor", "ExecutorReport": "executor",
    "StagedExecutor": "executor",
    "PDFComputer": "pipeline", "PDFConfig": "pipeline", "SliceResult": "pipeline",
    "CubeGeometry": "regions", "Plan": "regions", "Window": "regions",
    "WorkUnit": "regions", "build_plan": "regions", "iter_windows": "regions",
}

__all__ = [
    "TYPES_4", "TYPES_10", "Moments", "moments_from_values",
    "FitResult", "compute_pdf_and_error", "compute_pdf_with_predicted_type",
    "PDFComputer", "PDFConfig", "SliceResult",
    "StagedExecutor", "ExecutorConfig", "ExecutorReport",
    "CubeGeometry", "Window", "WorkUnit", "Plan", "build_plan", "iter_windows",
    "distributions", "executor", "fitting", "grouping", "ml_predict",
    "pdf_error", "pipeline", "regions", "reuse", "sampling",
]


def __getattr__(name):
    if name in _SUBMODULES:
        return importlib.import_module(f"{__name__}.{name}")
    if name in _NAMES:
        return getattr(importlib.import_module(f"{__name__}.{_NAMES[name]}"), name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
