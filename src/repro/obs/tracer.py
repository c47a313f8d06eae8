"""Span-tree round tracer + the ControlExplain change log.

The tracer records each scheduling round as one span on its track (track =
shard id; unsharded loops are track 0) with nested child spans for the
round's latency breakdown, on the **engine clock**:

* virtual engines (simulate/serving) — span boundaries are exact virtual
  seconds: ``[clock - cost, clock]`` with ``prefetch_stall`` / ``execute``
  children partitioning the interval (selection is free on the cost
  model's clock, so there is no ``select`` child);
* wall-clock engines (crossmatch, daemon) — span boundaries are
  ``perf_counter`` marks taken inside the round by
  :class:`~repro.obs.phases.PhaseSpans`: the round runs from the start of
  ``DispatchLoop.round`` to the end of its taps, and its children are the
  measured phases (select, fetch, gather, launch, readback, route,
  complete).  A daemon's ``submit`` spans, with their ``decompose`` child,
  are stored the same way.

Every span is ``(track, name, t0, dur, children, args)`` and every child
``(name, start offset from t0, dur)``, so children need not be contiguous
and a span's self time is ``dur`` minus its children's.  Storage is
append-only tuples (event-dict construction is deferred to export time —
see ``exporters.perfetto_trace``).  Both stores are bounded: past
``limit`` events are counted in ``dropped`` instead of growing without
bound under a long-lived daemon.

``ControlExplain`` is the "why did the controller move" channel: one entry
per ControlVector field change, stamped with the engine clock and a
telemetry-derived reason string ("alpha 0.2->0.35: rate=12/s oldest=514ms").
"""
from __future__ import annotations

__all__ = ["RoundTracer", "ControlExplain"]


class RoundTracer:
    """Bounded store of round spans and steal arrows, keyed by track."""

    __slots__ = ("limit", "dropped", "spans", "steals", "track_names")

    def __init__(self, limit: int = 100_000) -> None:
        self.limit = int(limit)
        self.dropped = 0
        # (track, name, t0, dur, children, args); children is a tuple of
        # (name, start offset from t0, dur).
        self.spans: list = []
        # (victim, thief, t, bucket_id, n_units)
        self.steals: list = []
        self.track_names: dict[int, str] = {}

    def name_track(self, track: int, name: str) -> None:
        self.track_names.setdefault(int(track), str(name))

    def note_span(
        self, track: int, name: str, t0: float, dur: float, children,
        args: dict,
    ) -> None:
        if len(self.spans) >= self.limit:
            self.dropped += 1
            return
        self.spans.append((track, name, t0, dur, children, args))

    def count(self, name: str) -> int:
        return sum(1 for s in self.spans if s[1] == name)

    def note_steal(
        self, victim: int, thief: int, t: float, bucket_id: int, n_units: int,
    ) -> None:
        if len(self.steals) >= self.limit:
            self.dropped += 1
            return
        self.steals.append((victim, thief, t, bucket_id, n_units))

    def tracks(self) -> list:
        ts = {s[0] for s in self.spans}
        for v, t, *_ in self.steals:
            ts.add(v)
            ts.add(t)
        return sorted(ts)


class ControlExplain:
    """One entry per ControlVector field change, with the trigger signal."""

    __slots__ = ("limit", "dropped", "events")

    def __init__(self, limit: int = 10_000) -> None:
        self.limit = int(limit)
        self.dropped = 0
        self.events: list = []

    def note(
        self, track, clock: float, field: str, old, new, reason: str,
    ) -> None:
        if len(self.events) >= self.limit:
            self.dropped += 1
            return
        self.events.append({
            "track": track,
            "clock": clock,
            "field": field,
            "from": old,
            "to": new,
            "message": f"{field} {old:g}->{new:g}: {reason}",
        })
