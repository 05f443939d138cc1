"""From a profiler trace to numbers.

``read_xplane`` is the thin reader: it turns the ``.xplane.pb`` that
``jax.profiler`` writes into plain tuples ``(plane, line, name, start_ns,
dur_ns)``.  Everything else is arithmetic on such tuples, so it is checked on
hand-made ones (``benchmarks/tests/test_trace_reduce.py``).

What a TPU trace looks like (looked at by hand, PR 25): one plane per chip
named ``/device:TPU:<n>``; on it the line ``XLA Modules`` holds one event per
run of a compiled program, named ``<module>(<fingerprint>)``, and the line
``XLA Ops`` one event per device operation inside it.  Host threads are lines
of the plane ``/host:CPU``.
"""

from __future__ import annotations

import glob
import os

DEVICE_PREFIX = '/device:TPU:'
OPS_LINE = 'XLA Ops'
MODULES_LINE = 'XLA Modules'


def find_xplane(trace_dir: str):
    """The newest ``.xplane.pb`` under ``trace_dir``, or None."""
    found = glob.glob(os.path.join(trace_dir, '**', '*.xplane.pb'),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def read_xplane(path: str) -> list:
    """Every event of the trace as ``(plane, line, name, start_ns, dur_ns)``."""
    from jax.profiler import ProfileData
    events = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                events.append((plane.name, line.name, ev.name,
                               float(ev.start_ns), float(ev.duration_ns)))
    return events


def device_planes(events: list, prefix: str = DEVICE_PREFIX) -> list:
    return sorted({e[0] for e in events if e[0].startswith(prefix)})


def on_line(events: list, plane: str, line: str) -> list:
    return [e for e in events if e[0] == plane and e[1] == line]


def merged(intervals: list) -> list:
    """Sorted, disjoint ``(start, end)`` covering the same points."""
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(s, e) for s, e in out]


def busy_intervals(events: list, plane: str, line: str = OPS_LINE) -> list:
    """When an operation ran on one device: the union of its op events."""
    return merged([(e[3], e[3] + e[4]) for e in on_line(events, plane, line)
                   if e[4] > 0])


def busy_seconds(events: list, line: str = OPS_LINE,
                 prefix: str = DEVICE_PREFIX):
    """Seconds in which an operation ran, averaged over the device planes
    that ran any; None where the trace holds no device operation."""
    per_plane = []
    for plane in device_planes(events, prefix):
        covered = sum(e - s for s, e in busy_intervals(events, plane, line))
        if covered > 0:
            per_plane.append(covered / 1e9)
    return sum(per_plane) / len(per_plane) if per_plane else None


def span_ns(events: list):
    """``(first start, last end)`` over every event of the trace."""
    if not events:
        return None
    return (min(e[3] for e in events), max(e[3] + e[4] for e in events))


def idle_share(busy_s: float, window_s: float) -> float:
    """1 - busy / window, in percent."""
    return 100.0 * (1.0 - busy_s / window_s)


def module_seconds(events: list, module: str, line: str = MODULES_LINE,
                   prefix: str = DEVICE_PREFIX) -> list:
    """Device durations, in seconds, of every run of the program whose name
    starts with ``module``, over all device planes."""
    return [e[4] / 1e9 for e in events
            if e[0].startswith(prefix) and e[1] == line
            and e[2].startswith(module)]


def top_ops(events: list, n: int = 10, line: str = OPS_LINE,
            prefix: str = DEVICE_PREFIX, name_chars: int = 160) -> list:
    """``[name, seconds]`` of the device operations that took most time,
    under the names the trace prints (an HLO line, cut to ``name_chars``)."""
    total = {}
    for e in events:
        if e[0].startswith(prefix) and e[1] == line:
            total[e[2]] = total.get(e[2], 0.0) + e[4] / 1e9
    return [[name[:name_chars], s] for name, s in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def gaps(intervals: list, window: tuple) -> list:
    """The idle ``(start, end)`` stretches of ``window`` that the disjoint,
    sorted ``intervals`` leave."""
    out, at = [], window[0]
    for start, end in intervals:
        if start > at:
            out.append((at, min(start, window[1])))
        at = max(at, end)
        if at >= window[1]:
            break
    if at < window[1]:
        out.append((at, window[1]))
    return [(s, e) for s, e in out if e > s]


def label_gap(gap: tuple, host_events: list) -> str:
    """What the host was doing in ``gap``: the host event that covers most of
    it, or ``unattributed``."""
    best, best_cover = 'unattributed', 0.0
    for e in host_events:
        cover = min(gap[1], e[3] + e[4]) - max(gap[0], e[3])
        if cover > best_cover:
            best, best_cover = e[2], cover
    return best if best_cover >= 0.5 * (gap[1] - gap[0]) else 'unattributed'


def idle_by_label(events: list, window: tuple, n: int = 10,
                  host_prefix: str = '/host:', line: str = OPS_LINE,
                  prefix: str = DEVICE_PREFIX, min_gap_ns: float = 1e6,
                  longest: int = 50) -> list:
    """``[label, seconds]``: the idle time of the first device plane that ran
    anything, summed by what the host was doing in each gap, most first.
    The ``longest`` gaps of ``min_gap_ns`` or more are labelled; the thousands
    of shorter ones lie between the operations of one program."""
    for plane in device_planes(events, prefix):
        intervals = busy_intervals(events, plane, line)
        if intervals:
            break
    else:
        return []
    idle = sorted(gaps(intervals, window), key=lambda g: g[0] - g[1])
    labelled = [g for g in idle[:longest] if g[1] - g[0] >= min_gap_ns]
    rest = sum(g[1] - g[0] for g in idle[len(labelled):])
    total = {'shorter gaps, not labelled': rest / 1e9} if rest else {}
    if labelled:
        # only a host event half as long as the shortest of them can label one
        floor = 0.5 * (labelled[-1][1] - labelled[-1][0])
        host = [e for e in events
                if e[0].startswith(host_prefix) and e[4] >= floor]
    for g in labelled:
        label = label_gap(g, host)
        total[label] = total.get(label, 0.0) + (g[1] - g[0]) / 1e9
    return [[label, s] for label, s in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def summarise(events: list, window_s: float) -> dict:
    """What ``run.py`` needs of one trace."""
    busy = busy_seconds(events)
    span = span_ns(events)
    return {
        'events': [e for e in events if e[0].startswith(DEVICE_PREFIX)],
        'busy_s': busy,
        'window_s': window_s,
        'span_s': (span[1] - span[0]) / 1e9 if span else None,
        'device_ops': top_ops(events),
        'idle_gaps': idle_by_label(events, span) if span else [],
    }
