"""From a profiler trace to the device's busy time, kernel time and idle gaps.

The JAX profiler writes an XSpace (``*.xplane.pb``).  Device planes are
named ``/device:TPU:<n>``; each holds an ``XLA Modules`` line (one event
per run of a jitted program, named after it) and an ``XLA Ops`` line
(one event per operation).  The host plane ``/host:CPU`` holds a line
per thread, and on it the benchmark's own spans, all named ``bench.*``
(``jax.profiler.TraceAnnotation``), on the same clock.

The reduction:

- busy time of a device: the union of its operation intervals inside the
  window, so overlapping events count once;
- idle share: 1 - busy / window;
- kernel time of a jitted module: the summed durations of that module's
  events which start inside the window;
- idle gaps: the holes in the busy union, each labelled by the benchmark
  spans that were open on the host at its middle.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float


@dataclasses.dataclass
class Trace:
    ops: dict[int, list[Event]]        # device -> operations
    modules: dict[int, list[Event]]    # device -> jitted-module runs
    spans: list[Event]                 # the benchmark's host spans

    def window(self) -> tuple[float, float]:
        """(start, end) of the ``bench.window`` span, in trace ns."""
        found = [s for s in self.spans if s.name == WINDOW_SPAN]
        if len(found) != 1:
            raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                             f"{len(found)}")
        return found[0].start_ns, found[0].end_ns


def _events(line) -> list[Event]:
    return [Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def from_profile(profile) -> Trace:
    """A :class:`Trace` from a ``jax.profiler.ProfileData``."""
    ops: dict[int, list[Event]] = {}
    modules: dict[int, list[Event]] = {}
    spans: list[Event] = []
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.setdefault(dev, []).extend(_events(line))
                elif line.name == MODULES_LINE:
                    modules.setdefault(dev, []).extend(_events(line))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans += [e for e in _events(line)
                          if e.name.startswith(SPAN_PREFIX)]
    return Trace(ops, modules, spans)


def load(trace_dir: str) -> Trace:
    """The one trace the profiler wrote under ``trace_dir``."""
    from jax.profiler import ProfileData

    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under "
                                f"{trace_dir}, found {found}")
    return from_profile(ProfileData.from_file(found[0]))


def union(events: list[Event], lo: float, hi: float
          ) -> list[tuple[float, float]]:
    """Merged intervals of ``events`` clipped to [lo, hi]."""
    iv = sorted((max(e.start_ns, lo), min(e.end_ns, hi)) for e in events
                if e.end_ns > lo and e.start_ns < hi)
    out: list[list[float]] = []
    for a, b in iv:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_s(events: list[Event], lo: float, hi: float) -> float:
    return sum(b - a for a, b in union(events, lo, hi)) / 1e9


def gaps(events: list[Event], lo: float, hi: float
         ) -> list[tuple[float, float]]:
    """The idle intervals of [lo, hi]: the holes in the busy union."""
    out, t = [], lo
    for a, b in union(events, lo, hi):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def module_name(event_name: str) -> str:
    """``jit_dict_decode(123)`` -> ``dict_decode``."""
    name = event_name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def module_s(trace: Trace, module: str, lo: float, hi: float) -> float:
    """Seconds of device time of jitted module ``module`` whose runs
    started in [lo, hi], over every device."""
    return sum(e.end_ns - e.start_ns for evs in trace.modules.values()
               for e in evs
               if module_name(e.name) == module and lo <= e.start_ns <= hi
               ) / 1e9


def op_names(ops: list[Event], modules: list[Event]) -> list[str]:
    """``<module>/<instruction>`` for each op: the jitted module whose run
    covers the op's start, and the HLO instruction's name without its
    shapes (``%fusion.2 = s32[...] fusion(...)`` -> ``%fusion.2``)."""
    runs = sorted(modules, key=lambda m: m.start_ns)
    starts = [m.start_ns for m in runs]
    out = []
    for e in ops:
        i = bisect.bisect_right(starts, e.start_ns) - 1
        mod = module_name(runs[i].name) if i >= 0 and \
            e.start_ns < runs[i].end_ns else "?"
        out.append(f"{mod}/{e.name.split(' = ', 1)[0]}")
    return out


def label(spans: list[Event], t: float) -> str:
    """What the host was doing at ``t``: the benchmark spans open then,
    other than the window, with how many of each (threads overlap)."""
    open_ = collections.Counter(s.name for s in spans
                                if s.start_ns <= t < s.end_ns
                                and s.name != WINDOW_SPAN)
    if not open_:
        return "no bench span"
    return "+".join(f"{n}x{c}" if c > 1 else n
                    for n, c in sorted(open_.items()))


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: dict[int, float]              # device -> busy seconds
    device_ops: list[list]                # [[name, seconds]] top 10
    idle_gaps: list[list]                 # [[label, seconds]] top 10

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s.values()) / len(self.busy_s)

    def idle_pct(self, device: int | None = None) -> float:
        busy = self.mean_busy_s if device is None else self.busy_s[device]
        return 100.0 * (1.0 - busy / self.window_s)


def summarize(trace: Trace, devices: list[int], top: int = 10) -> Summary:
    """Busy time per device over the window, the operations that took the
    most device time (an op nested in a loop counts beside its loop), and
    the longest idle gaps labelled by host span.  Where more than one
    device is used, names carry the device."""
    lo, hi = trace.window()
    many = len(devices) > 1
    busy = {d: busy_s(trace.ops.get(d, []), lo, hi) for d in devices}
    per_op: collections.Counter = collections.Counter()
    holes = []
    for d in devices:
        tag = f"TPU{d} " if many else ""
        ops = trace.ops.get(d, [])
        for e, name in zip(ops, op_names(ops, trace.modules.get(d, []))):
            if e.end_ns > lo and e.start_ns < hi:
                per_op[tag + name] += (min(e.end_ns, hi)
                                       - max(e.start_ns, lo)) / 1e9
        for a, b in gaps(trace.ops.get(d, []), lo, hi):
            holes.append([tag + label(trace.spans, (a + b) / 2),
                          (b - a) / 1e9])
    holes.sort(key=lambda g: -g[1])
    return Summary(window_s=(hi - lo) / 1e9, busy_s=busy,
                   device_ops=[[n, s] for n, s in per_op.most_common(top)],
                   idle_gaps=holes[:top])
