"""The program's own host spans in a traced window, for the per-layer readers.

The library writes ``knn:<name>`` spans (``repro.core.spans``) through
``jax.profiler.TraceAnnotation``, the mechanism of the harness's ``bench:``
spans, so both share the device trace's clock. ``devtrace.reduce`` keeps
only the ``bench:`` spans; this module reads the ``knn:`` ones from the same
``.xplane.pb``, which ``harness.run`` writes under ``.cache/trace``.

``window_spans(rec)`` gives ``{name: [seconds, count]}`` over the run's
``bench:window``: each span clipped to the window, counted when anything of
it is left. A program without ``knn:`` spans gives an empty dict, so its
readers read nothing.

    python3 benchmarks/chip/spantrace.py [trace_dir]

prints the window's span totals and its device idle gaps, each put down to
the innermost span (``knn:`` or ``bench:``) covering the gap's middle.
"""
from __future__ import annotations

import bisect
import functools
import json
import sys
from collections import defaultdict
from pathlib import Path
from typing import NamedTuple

import devtrace

HERE = Path(__file__).resolve().parent
TRACE_DIR = HERE / ".cache" / "trace"    # where harness.run writes its trace
PREFIXES = ("knn:", "bench:")
WINDOW = "bench:window"


class Span(NamedTuple):
    name: str
    start_ns: float
    dur_ns: float


def span_name(name: str) -> str:
    """``knn:flush#epoch=3#`` -> ``knn:flush``: the annotation's metadata off."""
    return name.split("#", 1)[0]


def events(path: Path) -> list[Span]:
    """The host spans of both prefixes in a ``.xplane.pb``, by start."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIXES):
                    out.append(Span(span_name(ev.name), float(ev.start_ns),
                                    float(ev.duration_ns)))
    return sorted(out, key=lambda s: s.start_ns)


def window_of(spans: list[Span]) -> tuple[float, float]:
    w = [s for s in spans if s.name == WINDOW]
    if not w:
        raise ValueError("the trace holds no bench:window span")
    return w[0].start_ns, w[0].start_ns + w[0].dur_ns


def totals(spans: list[Span], window: tuple[float, float]) -> dict[str, list]:
    """``{name: [seconds, count]}`` of the ``knn:`` spans clipped to ``window``."""
    w0, w1 = window
    out: dict[str, list] = {}
    for s in spans:
        if not s.name.startswith("knn:"):
            continue
        a, b = max(s.start_ns, w0), min(s.start_ns + s.dur_ns, w1)
        if b > a:
            tot = out.setdefault(s.name, [0.0, 0])
            tot[0] += (b - a) * 1e-9
            tot[1] += 1
    return out


def innermost(spans: list[Span], starts: list[float], t: float) -> str | None:
    """The span covering ``t`` that started last, other than the window
    (spans nest by call, so that is the innermost); None if none does.
    ``starts`` are the spans' starts, in order."""
    for i in range(bisect.bisect_right(starts, t) - 1, -1, -1):
        s = spans[i]
        if s.name != WINDOW and s.start_ns + s.dur_ns >= t:
            return s.name
    return None


def idle_gaps(spans: list[Span], busy: list[tuple[float, float]],
              window: tuple[float, float]) -> dict[str, float]:
    """Seconds of each idle gap between the merged ``busy`` intervals of a
    device, put down to the innermost span covering the gap's middle."""
    w0, w1 = window
    starts = [s.start_ns for s in spans]
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    gaps: dict[str, float] = defaultdict(float)
    for a, b in zip(edges[::2], edges[1::2]):
        if b > a:
            gaps[innermost(spans, starts, (a + b) / 2) or "other"] += (b - a) * 1e-9
    return dict(sorted(gaps.items(), key=lambda kv: -kv[1]))


@functools.lru_cache(maxsize=1)
def _file_totals(path: str, mtime_ns: int) -> tuple[float, dict]:
    spans = events(Path(path))
    w0, w1 = window_of(spans)
    return (w1 - w0) * 1e-9, totals(spans, (w0, w1))


def window_spans(rec) -> dict[str, list] | None:
    """The ``knn:`` span totals of the run's traced window, or None for an
    untraced run. The trace file must be this run's: its window as long as
    the one ``rec.trace`` was reduced over."""
    t = rec.trace
    if t is None:
        return None
    carried = getattr(t, "spans", None)   # a Summary that carries them already
    if carried is not None:
        return carried
    try:
        path = devtrace.xplane_file(TRACE_DIR)
    except FileNotFoundError:
        return None
    window_s, spans = _file_totals(str(path), path.stat().st_mtime_ns)
    return spans if abs(window_s - t.window_s) < 1e-9 else None


def main(argv: list[str]) -> int:
    path = devtrace.xplane_file(Path(argv[0]) if argv else TRACE_DIR)
    spans = events(path)
    w0, w1 = window_of(spans)
    mods = [e for e in devtrace.events(path) if devtrace.is_module(e)]
    first = min(e.plane for e in mods)
    busy = devtrace._union([(max(e.start_ns, w0), min(e.start_ns + e.dur_ns, w1))
                            for e in mods if e.plane == first
                            and e.start_ns < w1 and e.start_ns + e.dur_ns > w0])
    print(json.dumps({"window_s": (w1 - w0) * 1e-9, "spans": totals(spans, (w0, w1)),
                      "idle_gaps": idle_gaps(spans, busy, (w0, w1))}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
