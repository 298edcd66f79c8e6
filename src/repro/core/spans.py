"""Named host spans of the engine and the build, on the profiler's clock.

``span(name, totals, **attrs)`` opens ``jax.profiler.TraceAnnotation("knn:" +
name, **attrs)``, so the span lands in a profiler trace on the device
trace's clock, and adds its host seconds and one call to ``totals`` under
the full name. There is no switch: with no profiler session open an
annotation costs about a microsecond. A span times the host's part of the
work (dispatch, uploads, and the wait of a blocking readback); what the
device spends comes from the trace's programs, so no span blocks on the
device to look exact.

The names are stable (the benchmark's readers key on them); nesting is by
call:

===========================  ==================================================
``knn:query``                ``EngineCore.query_batch``; attrs ``batch``
                             (``query_batches`` before this one), ``epoch``, ``b``
``knn:query.ks``             the per-query k upload
``knn:query.gather``         the gather hook: routing, uploads, dispatch
``knn:query.readback``       after ``knn:query``, its sibling: the answer's one
                             device->host readback; attr ``bytes``
``knn:flush``                ``EngineCore.flush_updates`` to the publish; attrs
                             ``epoch`` (the one it publishes), ``staged``,
                             ``inserts``, ``deletes`` (the net delta)
``knn:flush.warm``           an engine's first flush only: compiling every
                             program a flush can dispatch; attr ``programs``
``knn:flush.scan``           the delete-hit row scan
``knn:flush.frontier``       the insert frontier (device rounds or host)
``knn:flush.frontier.round`` one frontier round; attrs ``round``, ``rows``
``knn:flush.frontier.part``  one program dispatch of a frontier round; attrs
                             ``rows`` (unpadded), ``t`` (the width bucket)
``knn:flush.purge_merge``    host side of the purge-merge: candidates,
                             padding, uploads, enqueue
``knn:flush.repair``         the repair rounds
``knn:flush.repair.round``   one repair round; attrs ``round``, ``rows``
``knn:flush.repair.part``    one program dispatch of a repair round; attrs
                             ``rows`` (unpadded), ``t`` (the width bucket)
``knn:flush.readback``       each blocking device->host readback of a flush;
                             attr ``bytes``
``knn:flush.publish``        layout hook, epoch swap, journal commit
``knn:build``                ``construct_jax.build_knn_tables_jax``
``knn:build.extras``         the object extras: host packing, two uploads
``knn:build.sweep``          one sweep's enqueue; attr ``direction``
===========================  ==================================================
"""
from __future__ import annotations

import time

from jax.profiler import TraceAnnotation

PREFIX = "knn:"


class Span:
    """One open span; ``s`` holds its host seconds once it has closed."""

    __slots__ = ("name", "s", "_totals", "_ann", "_t0")

    def __init__(self, name: str, totals: dict | None, attrs: dict):
        self.name = PREFIX + name
        self.s = 0.0
        self._totals = totals
        self._ann = TraceAnnotation(self.name, **attrs)
        self._t0 = 0.0

    def __enter__(self) -> "Span":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.s = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        if self._totals is not None:
            tot = self._totals.get(self.name)
            if tot is None:
                self._totals[self.name] = {"s": self.s, "n": 1}
            else:
                tot["s"] += self.s
                tot["n"] += 1


def span(name: str, totals: dict | None = None, **attrs) -> Span:
    """A span named ``knn:<name>``, added to ``totals`` (name -> {"s", "n"})
    when given. Attribute values are ints or strings."""
    return Span(name, totals, attrs)
