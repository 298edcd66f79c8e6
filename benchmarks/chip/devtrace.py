"""Reduction of a profiler trace to the numbers the per-layer metrics read.

``events(path)`` flattens the ``.xplane.pb`` that ``jax.profiler`` writes
into plain ``Event`` tuples; ``reduce(events, window)`` works on those, so a
small recorded excerpt (``tests/bench_chip/data``) checks the arithmetic
without a chip. On a ``/device:`` plane the ``XLA Modules`` line holds one
event per program run, named after the jitted function (``jit_serve_gather``,
``jit__sweep_program``), and the ``XLA Ops`` line the HLO ops inside them.
Busy time is the union of the program intervals (averaged over the device
planes), a program's device time the sum of its runs, and the op breakdown
sums the leaf ops (control flow, which contains other ops, left out). Host
spans are the harness's own ``TraceAnnotation`` names (``bench:<name>``);
each idle gap of the device is put down to the harness span that covers
its middle.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict
from pathlib import Path
from typing import NamedTuple

SPAN_PREFIX = "bench:"


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float


def xplane_file(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def events(path: Path) -> list[Event]:
    from jax.profiler import ProfileData

    out = []
    pd = ProfileData.from_file(str(path))
    for plane in pd.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name not in ("XLA Modules", "XLA Ops"):
                continue
            for ev in line.events:
                if not device and not ev.name.startswith(SPAN_PREFIX):
                    continue
                out.append(Event(plane.name, line.name, ev.name, float(ev.start_ns),
                                 float(ev.duration_ns)))
    return out


_CONTROL_FLOW = re.compile(r"^(while|conditional|call)$")


def is_op(ev: Event) -> bool:
    return ev.plane.startswith("/device:") and ev.line == "XLA Ops"


def is_module(ev: Event) -> bool:
    return ev.plane.startswith("/device:") and ev.line == "XLA Modules"


def op_label(hlo: str) -> tuple[str, str]:
    """``%copy.1 = f32[131045,20]{1,0:T(8,128)} copy(...)`` ->
    (``copy``, ``copy.1 copy f32[131045,20]``): the op's kind and a short
    label, its layouts dropped."""
    name, _, rest = hlo.partition(" = ")
    if rest.startswith("("):  # a tuple type: skip its balanced parentheses
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        typ, rest = rest[: i + 1], rest[i + 1:]
    else:
        typ, _, rest = rest.partition(" ")
    kind = rest.strip().split("(", 1)[0]
    typ = re.sub(r"\{[^{}]*\}", "", typ)
    return kind, f"{name.lstrip('%')} {kind} {typ[:60]}"


def program_name(module_event_name: str) -> str:
    """``jit_serve_gather(123)`` -> ``jit_serve_gather``."""
    return re.sub(r"\(\d+\)$", "", module_event_name)


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                     # averaged over the device planes
    programs_s: dict[str, float]      # module name -> summed device seconds
    program_calls: dict[str, int]
    device_ops: list[tuple[str, float]]
    idle_gaps: list[tuple[str, float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def reduce(evs: list[Event], window: tuple[float, float] | None = None) -> Summary:
    """Busy/idle union, per-program device time and the breakdown.

    ``window`` (start, end) in the trace's nanoseconds clips every interval;
    without it the window is the harness's ``bench:window`` span."""
    if window is None:
        spans = [e for e in evs if e.name == SPAN_PREFIX + "window"]
        if not spans:
            raise ValueError("the trace holds no bench:window span")
        window = (spans[0].start_ns, spans[0].start_ns + spans[0].dur_ns)
    w0, w1 = window

    def clip(e: Event) -> tuple[float, float] | None:
        s, t = max(e.start_ns, w0), min(e.start_ns + e.dur_ns, w1)
        return (s, t) if t > s else None

    per_plane: dict[str, list[tuple[float, float]]] = defaultdict(list)
    op_s: dict[str, float] = defaultdict(float)
    programs_s: dict[str, float] = defaultdict(float)
    calls: dict[str, int] = defaultdict(int)
    for e in evs:
        iv = clip(e)
        if iv is None:
            continue
        if is_op(e):
            kind, label = op_label(e.name)
            if not _CONTROL_FLOW.match(kind):
                op_s[label] += (iv[1] - iv[0]) * 1e-9
        elif is_module(e):
            per_plane[e.plane].append(iv)
            name = program_name(e.name)
            programs_s[name] += (iv[1] - iv[0]) * 1e-9
            calls[name] += 1
    if not per_plane:
        raise ValueError("no device program ran in the traced window")
    unions = {p: _union(iv) for p, iv in per_plane.items()}
    busy = sum(sum(t - s for s, t in u) for u in unions.values()) / len(unions) * 1e-9

    # idle gaps of the first device plane, each put down to the harness span
    # covering its middle (the harness's spans inside the window follow one
    # another, so the last one to start before the middle is the only one
    # that can cover it)
    first = unions[sorted(unions)[0]]
    edges = [w0] + [x for iv in first for x in iv] + [w1]
    host = sorted((e for e in evs
                   if e.name.startswith(SPAN_PREFIX) and e.name != SPAN_PREFIX + "window"),
                  key=lambda e: e.start_ns)
    starts = [e.start_ns for e in host]
    gaps: dict[str, float] = defaultdict(float)
    for s, t in zip(edges[::2], edges[1::2]):
        if t <= s:
            continue
        mid = (s + t) / 2
        i = bisect.bisect_right(starts, mid) - 1
        covered = i >= 0 and host[i].start_ns + host[i].dur_ns >= mid
        gaps[host[i].name[len(SPAN_PREFIX):] if covered else "other"] += (t - s) * 1e-9
    top = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:10]  # noqa: E731
    return Summary(
        window_s=(w1 - w0) * 1e-9,
        busy_s=busy,
        programs_s=dict(programs_s),
        program_calls=dict(calls),
        device_ops=[[k, v] for k, v in top(op_s)],
        idle_gaps=[[k, v] for k, v in top(gaps)],
    )
