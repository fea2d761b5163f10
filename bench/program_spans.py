"""The program's own host spans (``repro.*``, listed in
``src/repro/tracing.py``), for the per-layer metrics that read them.

`bench.trace.Trace.from_xplane` keeps the benchmark's ``bench.`` spans only.
`spans(trace)` reads the program's spans from the same profiler file, found
under the harness's trace directory by the start of its ``bench.window``
span on the profiler clock, and keeps them on the trace for the next
reader.  A trace that holds ``repro.`` spans already is read as it is.  A
trace with no such file (a recorded test trace), or a program that opens no
such span, has none, and the readers then return None.

This second read of the profile is interim.  Once `bench/trace.py` keeps
``repro.`` spans too (``SPAN_PREFIX = ("bench.", "repro.")``, a change of
the benchmark's own reader), `spans` reduces to its first branch:
`_from_profile`, `read_xplane` and the ``_program_spans`` cache on the
trace are then to be deleted.

Every value is counted over the window: a span counts when it lies inside
``bench.window``, and a mean per unit divides by the units
(``ctx["unit_span"]``) inside it, as the other readers count them.
"""
from __future__ import annotations

import glob
import os

from bench.harness import TRACE_DIR, quantile
from bench.trace import Span

PREFIX = "repro."


def spans(trace) -> list:
    """The program's spans of ``trace``, sorted by start."""
    own = [s for s in trace.spans if s.name.startswith(PREFIX)]
    if own:
        return own
    found = getattr(trace, "_program_spans", None)
    if found is None:
        found = trace._program_spans = _from_profile(trace)
    return found


def _from_profile(trace) -> list:
    win = trace.window()
    if win is None:
        return []
    for path in sorted(glob.glob(os.path.join(
            TRACE_DIR, "**", "*.xplane.pb"), recursive=True)):
        try:
            start, found = read_xplane(path)
        except (OSError, RuntimeError, ValueError):
            continue        # a trace another run is writing or removing
        if start == win.start:
            return found
    return []


def read_xplane(path: str) -> tuple[float | None, list]:
    """(start of the ``bench.window`` span, the program's spans) of one
    profiler file's host threads."""
    from jax.profiler import ProfileData
    start, found = None, []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    found.append(Span(ev.name, float(ev.start_ns),
                                      float(ev.duration_ns)))
                elif ev.name == "bench.window" and start is None:
                    start = float(ev.start_ns)
    found.sort(key=lambda s: s.start)
    return start, found


def durations(trace, name: str) -> list[float]:
    """Durations (ns) of the program's spans ``name`` inside the window."""
    win = trace.window()
    if win is None:
        return []
    return [s.dur for s in spans(trace) if s.name == name
            and s.start >= win.start and s.end <= win.end]


def per_unit_ms(trace, ctx, *names: str) -> float | None:
    """Summed duration of the spans ``names`` inside the window, over the
    units inside it, in ms."""
    win = trace.window()
    total = [d for n in names for d in durations(trace, n)]
    if win is None or not total:
        return None
    units = [s for s in trace.spans_named(ctx["unit_span"])
             if s.start >= win.start and s.end <= win.end]
    if not units:
        return None
    return sum(total) / len(units) * 1e-6


def mean_ms(trace, name: str) -> float | None:
    """Mean duration of the spans ``name`` inside the window, in ms."""
    d = durations(trace, name)
    return sum(d) / len(d) * 1e-6 if d else None


def quantile_ms(trace, name: str, q: float) -> float | None:
    """The ``q``-th percentile of the spans ``name`` inside the window, in
    ms (`harness.quantile`)."""
    d = durations(trace, name)
    return quantile(d, q) * 1e-6 if d else None
