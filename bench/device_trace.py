"""Reduction of a profiler trace to the benchmark's device numbers.

The JAX profiler writes one ``.xplane.pb`` per traced process.  Its device
planes (``/device:TPU:<n>``) hold a line ``XLA Ops`` with one event per
operation the device ran, start and duration in nanoseconds on the host's
clock; the host plane (``/host:CPU``) holds the ``TraceAnnotation`` spans
the harness wrote around the window and around each round.

- busy time: the union of a device's operation intervals inside the
  window; idle share is one minus busy over the window's length;
- kernel time: the summed durations of the operations whose name, or
  whose ``long_name`` / ``tf_op`` / ``kernel_name`` stat, holds a
  kernel's name;
- idle gaps: the stretches of the window that no operation covers, each
  named by what the host was doing then: compiling (from the compile
  events' own times, moved onto the trace's clock), inside a round
  without a finer span (``unattributed``), or between rounds (the
  harness).
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

WINDOW_SPAN = "bench.window"
ROUND_SPAN = "bench.round"
OPS_LINE = "XLA Ops"
LABEL_STATS = ("long_name", "tf_op", "kernel_name")


def _label(ev) -> str:
    """The event's name-like stats, joined (empty where it has none)."""
    try:
        return " ".join(str(v) for k, v in ev.stats if k in LABEL_STATS)
    except (TypeError, ValueError):
        return ""


# ------------------------------------------------------ interval algebra
def union(intervals) -> list[tuple[int, int]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def covered(intervals) -> int:
    return sum(e - s for s, e in union(intervals))


def gaps(busy, lo: int, hi: int) -> list[tuple[int, int]]:
    """The parts of [lo, hi) that the disjoint sorted ``busy`` leaves."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def attribute(gap: tuple[int, int], compiles, rounds) -> str:
    """What the host was doing in an idle gap: compiling where compiles
    cover most of it; else inside or outside a round."""
    s, e = gap
    if 2 * covered(clip(compiles, s, e)) >= e - s:
        return "compile"
    mid = (s + e) // 2
    for i, (rs, re_) in enumerate(rounds):
        if rs <= mid < re_:
            return f"round {i + 1}: host, unattributed"
    return "harness, between rounds"


# ------------------------------------------------------------ the trace
@dataclass
class TraceSummary:
    window: tuple[int, int]                  # ns on the trace's clock
    devices: int
    busy_ns: list[int]                       # per device
    op_ns: dict[str, int]                    # per op name, summed
    op_count: dict[str, int]
    idle_gaps: list[tuple[str, int]]         # (what the host did, ns)
    rounds: list[tuple[int, int]] = field(default_factory=list)
    op_labels: dict[str, str] = field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        return sum(self.busy_ns) / len(self.busy_ns) / 1e9

    def kernel(self, pattern: str) -> tuple[float, int]:
        """Seconds and number of the operations whose name holds
        ``pattern``, over all devices."""
        hits = [n for n in self.op_ns
                if pattern in n or pattern in self.op_labels.get(n, "")]
        return (sum(self.op_ns[n] for n in hits) / 1e9,
                sum(self.op_count[n] for n in hits))


def find_xplane(directory: str) -> str:
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def summarize(path: str, compiles_wall=(), wall_at_window: int | None = None,
              top: int = 10) -> TraceSummary | None:
    """Reduce one xplane file; None where it holds no device plane (a run
    on the CPU).  ``compiles_wall``: ``(start, end)`` of each compile in
    ns of ``time.time_ns``; ``wall_at_window``: that clock as the window
    span opened, which moves the compiles onto the trace's clock."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans: dict[str, list[tuple[int, int]]] = {}
    device_ops, labels = [], {}
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        if ev.name not in labels:
                            labels[ev.name] = _label(ev)
                        ops.append((ev.name, int(ev.start_ns),
                                    int(ev.start_ns + ev.duration_ns)))
            if ops:             # planes that ran no operation are left out
                device_ops.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in (WINDOW_SPAN, ROUND_SPAN):
                        spans.setdefault(ev.name, []).append(
                            (int(ev.start_ns),
                             int(ev.start_ns + ev.duration_ns)))
    if not device_ops:
        return None
    if WINDOW_SPAN not in spans:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    lo, hi = spans[WINDOW_SPAN][0]
    rounds = sorted(spans.get(ROUND_SPAN, []))
    compiles = []
    if wall_at_window is not None:
        shift = lo - wall_at_window
        compiles = [(s + shift, e + shift) for s, e in compiles_wall]
    summary = reduce_ops(device_ops, (lo, hi), rounds, compiles, top)
    summary.op_labels = labels
    return summary


def summarize_dir(directory: str, compiles_wall=(),
                  wall_at_window: int | None = None) -> TraceSummary | None:
    return summarize(find_xplane(directory), compiles_wall, wall_at_window)


def reduce_ops(device_ops, window, rounds, compiles, top: int = 10):
    """The summary from per-device ``(name, start, end)`` operation lists."""
    lo, hi = window
    busy_ns, op_ns, op_count = [], {}, {}
    all_gaps = []
    for ops in device_ops:
        inside = [(n, s, e) for n, s, e in ops if e > lo and s < hi]
        merged = union(clip([(s, e) for _, s, e in inside], lo, hi))
        busy_ns.append(sum(e - s for s, e in merged))
        for n, s, e in inside:
            op_ns[n] = op_ns.get(n, 0) + (min(e, hi) - max(s, lo))
            op_count[n] = op_count.get(n, 0) + 1
        all_gaps += gaps(merged, lo, hi)
    all_gaps.sort(key=lambda g: g[0] - g[1])
    idle = [(attribute(g, compiles, rounds), g[1] - g[0])
            for g in all_gaps[:top]]
    return TraceSummary(window=(lo, hi), devices=len(device_ops),
                        busy_ns=busy_ns, op_ns=op_ns, op_count=op_count,
                        idle_gaps=idle, rounds=list(rounds))


def top_ops(summary: TraceSummary, top: int = 10) -> list[list]:
    ranked = sorted(summary.op_ns.items(), key=lambda kv: -kv[1])[:top]
    return [[name, ns / 1e9] for name, ns in ranked]
