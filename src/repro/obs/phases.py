"""Wall-clock phase spans inside a served round and a submit.

The round taps see a round only once it is over, so they cannot say where
its wall time went.  A :class:`PhaseSpans` is handed to the code that does
the work instead: ``DispatchLoop.phases`` (select, complete),
``CrossMatchEngine``'s executors (fetch, gather, launch, readback, route)
and ``ServiceDaemon`` (submit, decompose).  Each of them holds it as an
attribute that is ``None`` with obs off, so the off path pays one
``is None`` test per phase and never imports this module.

Every span is written to two places:

* ``perf_counter`` marks: the duration is observed in
  ``liferaft_phase_seconds{track, phase}`` and, inside a parent span
  (``round`` or ``submit``), stored as a ``(name, start offset, duration)``
  child of the parent in the :class:`~repro.obs.tracer.RoundTracer`;
* a ``jax.profiler.TraceAnnotation("liferaft.<phase>")``, which puts the
  span in the profiler's own trace on the device trace's clock, so a gap
  in device activity is labelled with the phase the host was in.

Children of one parent are sequential and never overlap: ``phase()``
closes the open child before it opens the next one.  Time of the parent
outside every child is its self time.
"""
from __future__ import annotations

from time import perf_counter

__all__ = ["PHASE_BUCKETS", "PhaseSpans"]

# Phases run from tens of microseconds (select) to seconds (a launch that
# compiles), below the 0.5 ms floor of the default ladder.
PHASE_BUCKETS = (
    1e-5, 2.5e-5, 5e-5, 1e-4, 2.5e-4, 5e-4, 1e-3, 2.5e-3, 5e-3,
    0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
)


class PhaseSpans:
    """Phase spans of one track (one thread of control at a time)."""

    __slots__ = (
        "obs", "track", "tracer", "_ann", "_hist", "_h_round",
        "_parent", "_child",
    )

    def __init__(self, obs, track: int = 0) -> None:
        from jax.profiler import TraceAnnotation

        self.obs = obs
        self.track = int(track)
        self.tracer = obs.tracer
        self._ann = TraceAnnotation
        self._hist: dict = {}
        self._h_round = None
        # (name, t0, annotation, children, args) of the open parent span
        self._parent = None
        # (name, t0, annotation) of the open child span
        self._child = None

    # -- parents ----------------------------------------------------------
    def begin_round(self, index: int) -> None:
        """Open the ``round`` span; ``index`` is the loop's round count."""
        self._open_parent("round", {"round": int(index)})

    def selected(self, decisions) -> None:
        """Close ``select`` and name the round's buckets.  When select
        found none the loop returns without a round: both spans are
        dropped unrecorded, so ``select`` counts one per round."""
        if not decisions:
            self._child[2].__exit__(None, None, None)
            self._parent[2].__exit__(None, None, None)
            self._child = self._parent = None
            return
        self.end()
        ids = [int(d.bucket_id) for d in decisions]
        self._parent[2].set_metadata(buckets=" ".join(map(str, ids)))
        self._parent[4]["buckets"] = ids

    def end_round(self) -> None:
        """Close the open child and the round; the round is stored with
        its children and observed in ``liferaft_round_wall_seconds``, and
        the host-to-device byte counter catches up with the round."""
        if self._h_round is None:
            self._h_round = self.obs.registry.histogram(
                "liferaft_round_wall_seconds",
                "Wall time of one round, from the start of "
                "DispatchLoop.round to the end of its round taps",
                track=str(self.track),
            )
        self._close_parent(self._h_round)
        self.obs.note_h2d()

    def begin_submit(self, key: str) -> None:
        self._open_parent("submit", {"key": str(key)})

    def end_submit(self) -> None:
        self._close_parent(self._histogram("submit"))

    # -- children -----------------------------------------------------------
    def phase(self, name: str, **args) -> None:
        """Close the open child, if any, and open ``name``; a child already
        open under ``name`` goes on (``args`` are then ignored)."""
        if self._child is not None:
            if self._child[0] == name:
                return
            self.end()
        ann = self._ann(f"liferaft.{name}", **args)
        ann.__enter__()
        self._child = (name, perf_counter(), ann)

    def end(self) -> None:
        """Close the open child, if any."""
        if self._child is None:
            return
        name, t0, ann = self._child
        t1 = perf_counter()
        ann.__exit__(None, None, None)
        self._child = None
        dur = t1 - t0
        self._histogram(name).observe(dur)
        p = self._parent
        if p is not None:
            p[3].append((name, t0 - p[1], dur))

    # -- internals ----------------------------------------------------------
    def _histogram(self, phase: str):
        h = self._hist.get(phase)
        if h is None:
            h = self._hist[phase] = self.obs.registry.histogram(
                "liferaft_phase_seconds",
                "Wall time of one phase span inside a round or a submit",
                buckets=PHASE_BUCKETS, track=str(self.track), phase=phase,
            )
        return h

    def _open_parent(self, name: str, args: dict) -> None:
        ann = self._ann(f"liferaft.{name}", **args)
        ann.__enter__()
        self._parent = (name, perf_counter(), ann, [], args)

    def _close_parent(self, hist) -> None:
        self.end()
        name, t0, ann, children, args = self._parent
        t1 = perf_counter()
        ann.__exit__(None, None, None)
        self._parent = None
        hist.observe(t1 - t0)
        if self.tracer is not None:
            self.tracer.note_span(
                self.track, name, t0 - self.obs.epoch, t1 - t0,
                tuple(children), args,
            )
