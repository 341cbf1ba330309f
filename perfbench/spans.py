"""From a profiler trace to the program's host stages: self time by stage,
and idle gaps named by the stage each host thread was in.

The program marks each stage of its scan path with a host span named
``repro.<layer>.<stage>`` (``repro.trace``), nested as the calls nest;
the benchmark's own spans are ``bench.*``.  The profiler writes one line
of the host plane per host thread (the lines carry no usable name: every
Python thread is ``python``), on the clock of the device planes.

The reduction:

- self time of a span: its interval clipped to the window, less what the
  ``repro.*`` spans nested in it on the same thread cover; summed over
  threads;
- an idle gap's label: for each host thread, the innermost span open at
  the gap's middle, other than the window, counted over threads in the
  form of ``tracereduce.label`` (``bench.scanx4``,
  ``repro.decode.decompressx2+repro.kernel.fetch+repro.storage.read``);
  with only ``bench.*`` spans the two labels agree;
- unattributed idle: the device's idle time in the window during which
  no stage span (a ``repro.*`` span other than the task's) was open on
  any thread.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os

from perfbench import tracereduce

PREFIXES = (tracereduce.SPAN_PREFIX, "repro.")
TASK = "repro.scan.task"

#: per-layer reading -> the spans whose self time it sums, per Mrow scanned
STAGES = {
    "storage_read_s_per_mrow.scan": ("repro.storage.read",),
    "decompress_s_per_mrow.scan": ("repro.decode.decompress",),
    "host_decode_s_per_mrow.scan": ("repro.decode.host",),
    "kernel_stage_s_per_mrow.scan": ("repro.kernel.dict_decode",
                                     "repro.kernel.predicate",
                                     "repro.kernel.pack"),
    "kernel_fetch_s_per_mrow.scan": ("repro.kernel.fetch",),
}


@dataclasses.dataclass(frozen=True)
class Span(tracereduce.Event):
    thread: int = 0         # the host plane's line, one per thread


def from_profile(profile) -> list[Span]:
    """The ``bench.*`` and ``repro.*`` spans of the host plane of a
    ``jax.profiler.ProfileData``, each with its thread."""
    return [Span(e.name, e.start_ns, e.start_ns + e.duration_ns, thread)
            for plane in profile.planes
            if plane.name == tracereduce.HOST_PLANE
            for thread, line in enumerate(plane.lines)
            for e in line.events if e.name.startswith(PREFIXES)]


def load(trace_dir: str) -> tuple[tracereduce.Trace, list[Span]]:
    """The device events and the host spans of the one trace the profiler
    wrote under ``trace_dir``."""
    from jax.profiler import ProfileData

    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under "
                                f"{trace_dir}, found {found}")
    profile = ProfileData.from_file(found[0])
    return tracereduce.from_profile(profile), from_profile(profile)


def _threads(spans: list[Span]) -> dict[int, list[Span]]:
    out: dict[int, list[Span]] = collections.defaultdict(list)
    for s in spans:
        out[s.thread].append(s)
    return out


def _clipped(s: Span, lo: float, hi: float) -> float:
    return max(0.0, min(s.end_ns, hi) - max(s.start_ns, lo))


def self_ns(spans: list[Span], lo: float, hi: float
            ) -> list[tuple[Span, float]]:
    """Each span with its self time in ns: its interval clipped to
    [lo, hi], less the clipped intervals of the ``repro.*`` spans nested
    directly in it on its thread (theirs, in turn, less their own)."""
    out = []
    for evs in _threads(spans).values():
        stack: list[list] = []          # [span, self ns], open on the thread
        for s in sorted(evs, key=lambda e: (e.start_ns, -e.end_ns)):
            while stack and stack[-1][0].end_ns <= s.start_ns:
                out.append(tuple(stack.pop()))
            own = _clipped(s, lo, hi)
            if stack and s.end_ns <= stack[-1][0].end_ns \
                    and s.name.startswith("repro."):
                stack[-1][1] -= own
            stack.append([s, own])
        out += [tuple(e) for e in reversed(stack)]
    return out


def self_s_per_mrow(spans: list[Span], lo: float, hi: float, rows: int,
                    groups: dict[str, tuple[str, ...]] = STAGES
                    ) -> dict[str, float | None]:
    """For each group of span names (by default :data:`STAGES`), the self
    seconds of those spans in [lo, hi], summed over threads, per million
    ``rows``; None where there are no rows or no such span."""
    per_span = self_ns(spans, lo, hi)
    out = {}
    for reading, names in groups.items():
        found = [own for s, own in per_span if s.name in names]
        out[reading] = (sum(found) / 1e9 / (rows / 1e6)
                        if found and rows else None)
    return out


def label(spans: list[Span], t: float) -> str:
    """What the host was doing at ``t``: on each thread, the innermost
    span open then other than the window, with how many threads were in
    each."""
    innermost: dict[int, Span] = {}
    for s in spans:
        if s.start_ns <= t < s.end_ns and s.name != tracereduce.WINDOW_SPAN:
            cur = innermost.get(s.thread)
            if cur is None or (s.start_ns, -s.end_ns) > (cur.start_ns,
                                                        -cur.end_ns):
                innermost[s.thread] = s
    open_ = collections.Counter(s.name for s in innermost.values())
    if not open_:
        return "no bench span"
    return "+".join(f"{n}x{c}" if c > 1 else n
                    for n, c in sorted(open_.items()))


def idle_gaps(trace: tracereduce.Trace, spans: list[Span],
              devices: list[int], top: int = 10) -> list[list]:
    """The ``top`` longest idle gaps of the window, longest first, as
    [label, seconds]; where more than one device is used, labels carry
    the device."""
    lo, hi = trace.window()
    many = len(devices) > 1
    holes = [(b - a, d, (a + b) / 2) for d in devices
             for a, b in tracereduce.gaps(trace.ops.get(d, []), lo, hi)]
    holes.sort(key=lambda h: -h[0])
    return [[(f"TPU{d} " if many else "") + label(spans, t), ns / 1e9]
            for ns, d, t in holes[:top]]


def _overlap_ns(a: list[tuple[float, float]],
                b: list[tuple[float, float]]) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0.0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def unattributed_idle_pct(trace: tracereduce.Trace, spans: list[Span],
                          device: int) -> float | None:
    """Share of ``device``'s idle time in the window during which no
    stage span was open on any thread; None where it was never idle."""
    lo, hi = trace.window()
    idle = tracereduce.gaps(trace.ops.get(device, []), lo, hi)
    idle_ns = sum(b - a for a, b in idle)
    if idle_ns <= 0:
        return None
    stages = tracereduce.union(
        [s for s in spans if s.name.startswith("repro.") and s.name != TASK],
        lo, hi)
    return 100.0 * (1.0 - _overlap_ns(idle, stages) / idle_ns)
