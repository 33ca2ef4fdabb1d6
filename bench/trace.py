"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

* Device busy time: the union of the intervals of the operations on each
  used chip's ``XLA Ops`` line, inside the traced window, averaged over
  the chips. The window is the benchmark's own host span ``bench.window``.
* Kernel time: the summed device durations of the operations whose HLO
  instruction is the kernel (``%moments_edges_stats.1 = ...`` is
  ``moments_edges_stats``); Pallas names the custom call after the kernel.
* Device operations by time, named ``<jit module>/<instruction>``, the
  module being the ``XLA Modules`` event that encloses the operation.
* Idle gaps: the stretches inside the window where no operation runs on
  the first used chip, each charged to the event name of the benchmark's
  main thread (the host line holding ``bench.window``) that covers most of
  it, or to ``host:python`` where traced calls cover less than half of it,
  as while the executor's Python (Select, persist hand-off) runs.

Only ``jax.profiler.ProfileData`` is needed to read a trace.
"""

from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field
from pathlib import Path

WINDOW_SPAN = "bench.window"
BENCH_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
UNTRACED = "host:python"


@dataclass
class Event:
    name: str
    start: int  # ns
    end: int  # ns


@dataclass
class Trace:
    devices: dict  # plane name -> [Event] of its XLA Ops line
    host: list  # [Event] of the host line holding the window span
    window: tuple  # (start_ns, end_ns)
    modules: dict = field(default_factory=dict)  # plane name -> [Event]


def find_xplane(trace_dir: Path) -> Path:
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _events(line) -> list[Event]:
    return [Event(e.name, int(e.start_ns), int(e.end_ns)) for e in line.events]


def load(path: Path) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices, modules, main = {}, {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = _events(line)
                elif line.name == MODULES_LINE:
                    modules[plane.name] = _events(line)
        elif plane.name.startswith("/host:") and not main:
            for line in plane.lines:
                if any(e.name == WINDOW_SPAN for e in line.events):
                    main = _events(line)
                    break
    spans = [e for e in main if e.name == WINDOW_SPAN]
    if spans:
        window = (spans[0].start, spans[0].end)
    else:
        evs = [e for evs in devices.values() for e in evs]
        window = (min(e.start for e in evs), max(e.end for e in evs)) if evs else (0, 0)
    return Trace(devices, main, window, modules)


def used_devices(tr: Trace, chips: int) -> list[str]:
    def key(name):
        tail = name.rsplit(":", 1)[-1]
        return int(tail) if tail.isdigit() else 1 << 30

    return sorted(tr.devices, key=key)[:chips]


def _clip(events, lo, hi):
    for e in events:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            yield s, t


def union(intervals) -> list[tuple[int, int]]:
    merged: list[list[int]] = []
    for s, t in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], t)
        else:
            merged.append([s, t])
    return [(s, t) for s, t in merged]


def busy_seconds(tr: Trace, chips: int) -> float:
    """Union of operation intervals inside the window, averaged over chips."""
    lo, hi = tr.window
    names = used_devices(tr, chips)
    if not names:
        return 0.0
    total = 0
    for n in names:
        total += sum(t - s for s, t in union(_clip(tr.devices[n], lo, hi)))
    return total / len(names) / 1e9


def window_seconds(tr: Trace) -> float:
    return (tr.window[1] - tr.window[0]) / 1e9


def instruction(e: Event) -> str:
    """``%fit_error_counts.1 = f32[...] custom-call(...)`` -> ``fit_error_counts``."""
    name = e.name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"(\.(\d+|clone))+$", "", name)


def kernel_events(tr: Trace, chips: int, name: str) -> list[Event]:
    lo, hi = tr.window
    return [e for n in used_devices(tr, chips) for e in tr.devices[n]
            if instruction(e) == name and e.end > lo and e.start < hi]


def kernel_seconds(tr: Trace, chips: int, name: str) -> float:
    return sum(e.end - e.start for e in kernel_events(tr, chips, name)) / 1e9


def _module_name(e: Event) -> str:
    return e.name.split("(", 1)[0]


def top_ops(tr: Trace, chips: int, k: int = 10) -> list:
    """Device seconds per ``<module>/<instruction>``, most first, averaged
    over the used chips."""
    lo, hi = tr.window
    used = used_devices(tr, chips)
    acc: dict[str, int] = {}
    for n in used:
        mods = sorted(tr.modules.get(n, ()), key=lambda m: m.start)
        starts = [m.start for m in mods]
        for e in tr.devices[n]:
            d = min(e.end, hi) - max(e.start, lo)
            if d <= 0:
                continue
            i = bisect.bisect_right(starts, e.start) - 1
            mod = _module_name(mods[i]) if i >= 0 and mods[i].end >= e.end else "?"
            key = f"{mod}/{instruction(e)}"
            acc[key] = acc.get(key, 0) + d
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9 / max(1, len(used))] for name, ns in ranked]


def idle_gaps(tr: Trace, chips: int, k: int = 10) -> list:
    """Longest idle stretches of the first used chip, each named by the
    main-thread event name that covers most of it, if it covers at least
    half; else ``host:python``."""
    names = used_devices(tr, chips)
    if not names:
        return []
    lo, hi = tr.window
    busy = union(_clip(tr.devices[names[0]], lo, hi))
    gaps, cur = [], lo
    for s, t in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, t)
    if hi > cur:
        gaps.append((cur, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    host = [e for e in tr.host if not e.name.startswith(BENCH_PREFIX)]
    out = []
    for s, t in gaps[:k]:
        cover: dict[str, int] = {}
        for e in host:
            ov = min(e.end, t) - max(e.start, s)
            if ov > 0:
                cover[e.name] = cover.get(e.name, 0) + ov
        best = max(cover, key=cover.get, default=None)
        if best is None or 2 * cover[best] < t - s:
            best = UNTRACED
        out.append([best, (t - s) / 1e9])
    return out


def summarize(path: Path, chips: int) -> dict:
    tr = load(path)
    return {"trace": tr, "busy_s": busy_seconds(tr, chips),
            "window_s": window_seconds(tr),
            "device_ops": top_ops(tr, chips), "idle_gaps": idle_gaps(tr, chips)}
