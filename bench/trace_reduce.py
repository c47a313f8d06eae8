"""From a JAX profiler trace to the device's busy and idle time.

``load_events`` reads an ``.xplane.pb`` with nothing but JAX
(``jax.profiler.ProfileData``) into flat events.  ``reduce`` takes the
window from the host span ``bench.window`` and, inside it:

- busy: the union of the intervals in which an op ran on a device (the
  device planes' ``XLA Ops`` line), averaged over the devices;
- ops: device seconds per HLO instruction name; modules: device seconds
  per jitted program (the ``XLA Modules`` line), shapes and hashes cut;
- idle gaps: the stretches of the window in which no op ran, each put to
  what the host thread that holds the benchmark's spans was doing at its
  midpoint: the benchmark's span (``bench.step``, ``bench.submit``,
  ``bench.poll``, ``bench.idle``) and, inside it, the innermost runtime
  event that covers it.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import pathlib

__all__ = ["Event", "find_xplane", "load_events", "reduce", "union", "breakdown"]

WINDOW = "bench.window"
SPAN_PREFIX = "bench."
DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def find_xplane(trace_dir) -> pathlib.Path:
    found = sorted(pathlib.Path(trace_dir).glob("**/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_events(path) -> list[Event]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    return [
        Event(plane.name, line.name, ev.name, float(ev.start_ns), float(ev.duration_ns))
        for plane in data.planes
        for line in plane.lines
        for ev in line.events
    ]


def op_name(name: str) -> str:
    """An op's HLO instruction name without its shapes and operands
    (``%copy.2 = f32[...] copy(...)`` is ``copy.2``); a module's name
    without the hash JAX appends (``jit_f(123)`` is ``jit_f``)."""
    name = name.split(" = ", 1)[0].lstrip("%")
    return name.split("(", 1)[0] if name.endswith(")") else name


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s, e, w0, w1):
    return max(s, w0), min(e, w1)


class _Activity:
    """What the benchmark's host thread was doing at a time: its span
    (``bench.*``; they do not overlap) and the innermost runtime event
    inside that span that covers the time."""

    def __init__(self, thread_events: list[Event]) -> None:
        self.spans = sorted(
            (e for e in thread_events
             if e.name.startswith(SPAN_PREFIX) and e.name != WINDOW),
            key=lambda e: e.start_ns,
        )
        self.starts = [e.start_ns for e in self.spans]
        self.inner: dict[int, list[Event]] = collections.defaultdict(list)
        for e in thread_events:
            if e.name.startswith(SPAN_PREFIX):
                continue
            i = bisect.bisect_right(self.starts, e.start_ns) - 1
            if i >= 0 and e.end_ns <= self.spans[i].end_ns:
                self.inner[i].append(e)

    def at(self, t: float) -> str:
        i = bisect.bisect_right(self.starts, t) - 1
        if i < 0 or t >= self.spans[i].end_ns:
            return "outside spans"
        inner = [e for e in self.inner[i] if e.start_ns <= t < e.end_ns]
        if not inner:
            return self.spans[i].name
        return f"{self.spans[i].name} > {min(inner, key=lambda e: e.dur_ns).name}"


def reduce(events: list[Event]) -> dict:
    """Busy and idle time, per-op and per-module device seconds, and idle
    gaps by host activity, inside the ``bench.window`` span."""
    win = [e for e in events if e.name == WINDOW and not e.plane.startswith(DEVICE_PREFIX)]
    if not win:
        raise ValueError(f"no {WINDOW!r} span in the trace")
    w = max(win, key=lambda e: e.dur_ns)
    w0, w1 = w.start_ns, w.end_ns
    activity = _Activity([e for e in events if e.plane == w.plane and e.line == w.line])

    devices = sorted({e.plane for e in events if e.plane.startswith(DEVICE_PREFIX)
                      and e.line == OPS_LINE})
    ops: dict[str, float] = collections.defaultdict(float)
    modules: dict[str, float] = collections.defaultdict(float)
    busy_ns = 0.0
    gaps: list[tuple[float, float]] = []
    for dev in devices:
        ivs = []
        for e in events:
            if e.plane != dev or e.line not in (OPS_LINE, MODULES_LINE):
                continue
            s, t = _clip(e.start_ns, e.end_ns, w0, w1)
            if t <= s:
                continue
            if e.line == OPS_LINE:
                ops[op_name(e.name)] += (t - s) * 1e-9
                ivs.append((s, t))
            else:
                modules[op_name(e.name)] += (t - s) * 1e-9
        merged = union(ivs)
        busy_ns += sum(t - s for s, t in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n_dev = max(len(devices), 1)
    by_label: dict[str, float] = collections.defaultdict(float)
    for s, t in gaps:
        by_label[activity.at((s + t) / 2)] += (t - s) * 1e-9 / n_dev
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_ns * 1e-9 / n_dev,
        "devices": devices,
        "ops": dict(ops),
        "modules": dict(modules),
        "idle_by_activity": dict(by_label),
        "n_gaps": len(gaps),
        "longest_gap_s": max(((t - s) * 1e-9 for s, t in gaps), default=0.0),
    }


def breakdown(red: dict, top: int = 10) -> dict:
    """The result line's ``breakdown``: the device ops that took most time
    and the idle time by what the host was doing, ``top`` of each."""

    def first(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"device_ops": first(red["ops"]), "idle_gaps": first(red["idle_by_activity"])}

