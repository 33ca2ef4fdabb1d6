"""Step/window heartbeat monitoring + straggler policy.

XLA steps are SPMD-synchronous, so intra-step straggler mitigation happens at
the *work-unit* level (a window of the PDF pipeline, a data shard, a
checkpoint write): the host records a heartbeat per unit, and units that
exceed ``k x median`` of the trailing distribution are flagged for
re-dispatch (the PDF pipeline's windows are idempotent — re-running one is
safe, results overwrite byte-identically because data loading is
deterministic).

On a real cluster the same monitor ingests per-host heartbeats; here it is
driven by the single-process loops and unit-tested with synthetic timings.

``SpanRecorder`` names the host work between heartbeats: each span is a
``jax.profiler.TraceAnnotation`` (so a profiler trace shows it on the same
clock as the device operations, on the thread that ran it) whose two
``perf_counter`` reads also feed in-memory totals per span name, and the
heartbeats and stage totals take their times from those same reads.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass, field

from jax.profiler import TraceAnnotation

# Trailing-latency reservoir per monitor: enough samples for stable p99 at
# serving rates while bounding memory on long-lived daemons (a PDFServer's
# request monitor outlives any single run).
HISTORY_LIMIT = 8192


def percentiles(durations, qs=(0.5, 0.99)) -> dict[str, float]:
    """``{"p50": ..., "p99": ...}`` over a duration sample (nearest-rank on
    the sorted sample; empty input -> zeros). Shared by ``SessionReport``
    and the serve layer's stats so every latency surface quotes the same
    estimator."""
    s = sorted(durations)
    if not s:
        return {f"p{int(q * 100)}": 0.0 for q in qs}
    return {
        f"p{int(q * 100)}": s[min(len(s) - 1, int(q * (len(s) - 1) + 0.5))]
        for q in qs
    }


@dataclass(frozen=True)
class StragglerPolicy:
    window: int = 32  # trailing sample count for the median
    threshold: float = 3.0  # flag units slower than threshold x median
    min_samples: int = 5
    grace_seconds: float = 1.0  # never flag below this absolute duration


@dataclass
class StepMonitor:
    policy: StragglerPolicy = field(default_factory=StragglerPolicy)

    def __post_init__(self):
        self._durations: deque[float] = deque(maxlen=self.policy.window)
        # Separate, larger reservoir for percentile reporting: the straggler
        # median deliberately tracks only the trailing `policy.window` units,
        # but p50/p99 need the run's full distribution (bounded).
        self._history: deque[float] = deque(maxlen=HISTORY_LIMIT)
        self._inflight: dict[str, float] = {}
        self.flagged: list[str] = []
        self.completed: int = 0

    # -- heartbeat API --------------------------------------------------------

    def start(self, unit_id: str, now: float | None = None):
        self._inflight[unit_id] = now if now is not None else time.monotonic()

    def finish(self, unit_id: str, now: float | None = None) -> float:
        now = now if now is not None else time.monotonic()
        dur = now - self._inflight.pop(unit_id)
        self._durations.append(dur)
        self._history.append(dur)
        self.completed += 1
        return dur

    def abandon(self, unit_id: str) -> None:
        """Drop an inflight unit without recording a duration — for failed
        or superseded attempts (a retry, a losing speculative launch). The
        duration of an attempt that *didn't complete* must not enter the
        straggler median: an injected 10s stall recorded as a sample would
        triple the re-dispatch limit for every unit after it."""
        self._inflight.pop(unit_id, None)

    @property
    def history(self) -> tuple[float, ...]:
        """Completed-unit durations (trailing ``HISTORY_LIMIT``), oldest
        first — the percentile reservoir."""
        return tuple(self._history)

    def percentiles(self, qs=(0.5, 0.99)) -> dict[str, float]:
        """p50/p99 (by default) over every completed unit this monitor has
        seen — the per-stage latency surface of ``SessionReport`` and the
        serve-layer stats."""
        return percentiles(self._history, qs)

    def median(self) -> float | None:
        if len(self._durations) < self.policy.min_samples:
            return None
        s = sorted(self._durations)
        return s[len(s) // 2]

    def check_stragglers(self, now: float | None = None) -> list[str]:
        """Inflight units exceeding threshold x median -> flagged for
        re-dispatch. Idempotent units may simply be re-run."""
        now = now if now is not None else time.monotonic()
        med = self.median()
        if med is None:
            return []
        limit = max(self.policy.threshold * med, self.policy.grace_seconds)
        out = [u for u, t0 in self._inflight.items() if now - t0 > limit]
        for u in out:
            if u not in self.flagged:
                self.flagged.append(u)
        return out


class Span:
    """One open span: ``start`` is its first ``perf_counter`` read, ``end``
    its last (set when the ``with`` block exits)."""

    __slots__ = ("_recorder", "_name", "_annotation", "start", "end")

    def __init__(self, recorder: "SpanRecorder", name: str, start: float | None,
                 ids: dict):
        self._recorder = recorder
        self._name = name
        self._annotation = TraceAnnotation(name, **ids)
        self.start = start
        self.end: float | None = None

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def __enter__(self) -> "Span":
        self._annotation.__enter__()
        if self.start is None:
            self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.end = time.perf_counter()
        self._annotation.__exit__(*exc)
        self._recorder._add(self._name, self.end - self.start)


class SpanRecorder:
    """Totals of named host spans and counters, shared by the threads of
    one executor or session (the prefetch and writer threads record into
    the same recorder as the main thread).

    ``span(name, **ids)`` opens a profiler annotation carrying ``ids`` as
    its arguments (the executor passes ``slice`` and ``line``, which link a
    window's spans across threads) and adds the span's duration and a count
    to ``spans[name]``. ``count(name, n)`` adds to ``counters[name]``."""

    def __init__(self):
        self._lock = threading.Lock()
        self.spans: dict[str, tuple[float, int]] = {}
        self.counters: dict[str, int] = {}

    def span(self, name: str, start: float | None = None, **ids) -> Span:
        """``start``: begin at a ``perf_counter`` read already taken (the
        end of the span before), so adjacent spans share the read and tile
        the time between them with no gap."""
        return Span(self, name, start, ids)

    def _add(self, name: str, seconds: float) -> None:
        with self._lock:
            total, n = self.spans.get(name, (0.0, 0))
            self.spans[name] = (total + seconds, n + 1)

    def count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + int(n)

    def snapshot(self) -> tuple[dict, dict]:
        with self._lock:
            return dict(self.spans), dict(self.counters)

    def since(self, snapshot: tuple[dict, dict]) -> tuple[dict, dict]:
        """``(spans, counters)`` recorded after ``snapshot``."""
        spans0, counters0 = snapshot
        spans, counters = self.snapshot()
        d_spans = {}
        for name, (total, n) in spans.items():
            t0, n0 = spans0.get(name, (0.0, 0))
            if n > n0:
                d_spans[name] = (total - t0, n - n0)
        d_counters = {k: v - counters0.get(k, 0) for k, v in counters.items()
                      if v != counters0.get(k, 0)}
        return d_spans, d_counters


def merge_totals(into_spans: dict, into_counters: dict, spans: dict,
                 counters: dict) -> None:
    """Add one ``(spans, counters)`` pair into running totals."""
    for name, (total, n) in spans.items():
        t0, n0 = into_spans.get(name, (0.0, 0))
        into_spans[name] = (t0 + total, n0 + n)
    for name, v in counters.items():
        into_counters[name] = into_counters.get(name, 0) + v
