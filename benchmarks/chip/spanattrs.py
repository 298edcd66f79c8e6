"""The attributes of the program's host spans in a traced window.

``spantrace`` totals the ``knn:`` spans by name alone. A reader that needs
what a span carries (``knn:flush``'s ``inserts``, a
``knn:flush.frontier.part``'s ``rows`` and ``t``) reads it here, from the
same ``.xplane.pb``. ``TraceAnnotation`` keeps its keyword arguments as the
event's stats; a name that carries them (``name#key=value,...#``) is read
the same way.

``window_attr_spans(rec)`` gives the ``knn:`` spans that overlap the run's
``bench:window``, by start, each with its attributes; None for an untraced
run.
"""
from __future__ import annotations

import functools
from pathlib import Path
from typing import NamedTuple

import devtrace
import spantrace


class AttrSpan(NamedTuple):
    name: str
    start_ns: float
    dur_ns: float
    attrs: dict


def _value(v):
    try:
        return int(v)
    except (TypeError, ValueError):
        return v


def parse(name: str, stats: dict) -> tuple[str, dict]:
    """An event's name and stats -> (the span's name, its attributes)."""
    base, _, meta = name.partition("#")
    attrs = {}
    for item in meta.strip("#").split(","):
        key, eq, value = item.partition("=")
        if eq:
            attrs[key] = _value(value)
    attrs.update((k, _value(v)) for k, v in stats.items())
    return base, attrs


def events(path: Path) -> list[AttrSpan]:
    """The host spans of both prefixes in a ``.xplane.pb``, by start."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(spantrace.PREFIXES):
                    name, attrs = parse(ev.name, dict(ev.stats))
                    out.append(AttrSpan(name, float(ev.start_ns), float(ev.duration_ns), attrs))
    return sorted(out, key=lambda s: s.start_ns)


def in_window(spans: list[AttrSpan], window: tuple[float, float]) -> list[AttrSpan]:
    w0, w1 = window
    return [s for s in spans if s.name.startswith("knn:")
            and s.start_ns < w1 and s.start_ns + s.dur_ns > w0]


def enclosing(spans: list[AttrSpan], inner: AttrSpan, name: str) -> AttrSpan | None:
    """The ``name`` span that covers ``inner``'s start (spans nest by call,
    so the last one to start before it)."""
    for s in reversed(spans):
        if s.name == name and s.start_ns <= inner.start_ns <= s.start_ns + s.dur_ns:
            return s
    return None


@functools.lru_cache(maxsize=1)
def _file_spans(path: str, mtime_ns: int) -> tuple[float, list[AttrSpan]]:
    spans = events(Path(path))
    w0, w1 = spantrace.window_of(spans)
    return (w1 - w0) * 1e-9, in_window(spans, (w0, w1))


def window_attr_spans(rec) -> list[AttrSpan] | None:
    """The traced window's ``knn:`` spans with their attributes, or None for
    an untraced run. As ``spantrace.window_spans``, the trace file must be
    this run's, and a ``Summary`` may carry them already (``attr_spans``)."""
    t = rec.trace
    if t is None:
        return None
    carried = getattr(t, "attr_spans", None)
    if carried is not None:
        return carried
    try:
        path = devtrace.xplane_file(spantrace.TRACE_DIR)
    except FileNotFoundError:
        return None
    window_s, spans = _file_spans(str(path), path.stat().st_mtime_ns)
    return spans if abs(window_s - t.window_s) < 1e-9 else None
