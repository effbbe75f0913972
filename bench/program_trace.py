"""Reduction of a profiler trace to the program's own spans and programs.

``device_trace`` reads the device's operations and the harness's spans.
This module reads, from the same ``.xplane.pb``, what the program itself
writes there:

- the host plane's program spans: every event whose name starts with
  ``fedsdd.`` (``repro.analysis.spans``), on any host thread;
- each device plane's ``XLA Modules`` line: one event per execution of a
  device program, named ``jit_<program>(<fingerprint>)``.  A v5e trace's
  ``XLA Ops`` events carry the HLO instruction's text and no program
  name, so an operation belongs to the execution whose interval holds
  its start.

From those:

- per-program device time: the union of the operation intervals inside
  each program's executions, in the window, per round;
- programs per round: the ``XLA Modules`` executions that start inside a
  round (eager per-leaf operations are programs of their own);
- the idle gaps inside rounds named by the innermost program span that
  covers most of the gap (``round 1: fedsdd.local.reassemble``), and the
  share of in-round idle time that a span finer than ``fedsdd.round``
  covers.  Gaps that compiles cover, that fall between rounds or that no
  finer span covers keep ``device_trace``'s names.
"""
from __future__ import annotations

import bisect
from dataclasses import dataclass, field

from device_trace import (
    OPS_LINE, ROUND_SPAN, WINDOW_SPAN, clip, covered, gaps, union,
)

MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "fedsdd."
ROOT_SPAN = "fedsdd.round"
# the stable names of the round's device programs
PROGRAMS = ("fedsdd_bucket_scan", "fedsdd_eq2", "fedsdd_kd_precompute",
            "fedsdd_kd_scan")


def program_name(module: str) -> str:
    """``jit_fedsdd_eq2(1234)`` -> ``fedsdd_eq2``."""
    name = module.split("(", 1)[0]
    return name[len("jit_"):] if name.startswith("jit_") else name


@dataclass
class ProgramTrace:
    window: tuple[int, int]                      # ns on the trace's clock
    rounds: list[tuple[int, int]]
    spans: list[tuple[str, int, int]]            # the program's host spans
    modules: list[list[tuple[str, int, int]]]    # per device: executions
    ops: list[list[tuple[int, int]]]             # per device: operations
    compiles: list[tuple[int, int]] = field(default_factory=list)

    @property
    def n_rounds(self) -> int:
        return max(1, len(self.rounds))


def read(path: str, compiles_wall=(),
         wall_at_window: int | None = None) -> ProgramTrace | None:
    """The program's spans and programs in one xplane file; None where
    no device plane ran an operation.  ``compiles_wall`` and
    ``wall_at_window`` as in ``device_trace.summarize``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    harness: dict[str, list[tuple[int, int]]] = {}
    spans, modules, ops = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            mods, dev_ops = [], []
            for line in plane.lines:
                if line.name not in (MODULES_LINE, OPS_LINE):
                    continue
                for ev in line.events:
                    s = int(ev.start_ns)
                    e = int(ev.start_ns + ev.duration_ns)
                    if line.name == MODULES_LINE:
                        mods.append((program_name(ev.name), s, e))
                    else:
                        dev_ops.append((s, e))
            if dev_ops:
                modules.append(sorted(mods, key=lambda m: m[1]))
                ops.append(dev_ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s = int(ev.start_ns)
                    e = int(ev.start_ns + ev.duration_ns)
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, s, e))
                    elif ev.name in (WINDOW_SPAN, ROUND_SPAN):
                        harness.setdefault(ev.name, []).append((s, e))
    if not ops:
        return None
    if WINDOW_SPAN not in harness:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    window = harness[WINDOW_SPAN][0]
    compiles = []
    if wall_at_window is not None:
        shift = window[0] - wall_at_window
        compiles = [(s + shift, e + shift) for s, e in compiles_wall]
    return ProgramTrace(window, sorted(harness.get(ROUND_SPAN, [])),
                        sorted(spans, key=lambda x: x[1]), modules, ops,
                        compiles)


# ----------------------------------------------------------- programs
def program_seconds(pt: ProgramTrace) -> dict[str, float]:
    """Device seconds per round under each program name: the union of
    the operations inside its executions, in the window, averaged over
    the devices."""
    lo, hi = pt.window
    per: dict[str, list[tuple[int, int]]] = {}
    for mods, ops in zip(pt.modules, pt.ops):
        starts = [s for _, s, _ in mods]
        for s, e in ops:
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < mods[i][2]:
                per.setdefault(mods[i][0], []).append((s, e))
    scale = 1e9 * len(pt.ops) * pt.n_rounds
    return {name: covered(clip(iv, lo, hi)) / scale
            for name, iv in per.items()}


def busy_seconds(pt: ProgramTrace) -> float:
    """Device busy seconds per round: the union of all operations in the
    window, averaged over the devices."""
    lo, hi = pt.window
    busy = sum(covered(clip(ops, lo, hi)) for ops in pt.ops)
    return busy / (1e9 * len(pt.ops) * pt.n_rounds)


def named_share(pt: ProgramTrace) -> float:
    """Percent of the device's busy time inside the round's named
    programs (``PROGRAMS``)."""
    secs = program_seconds(pt)
    return 100.0 * sum(secs.get(p, 0.0) for p in PROGRAMS) / busy_seconds(pt)


def round_programs(pt: ProgramTrace) -> float:
    """Device program executions that start inside a round, per round
    and device."""
    n = 0
    for mods in pt.modules:
        n += sum(1 for _, s, _ in mods
                 if any(rs <= s < re_ for rs, re_ in pt.rounds))
    return n / (len(pt.modules) * pt.n_rounds)


# ---------------------------------------------------------- idle gaps
def _fine_spans(pt: ProgramTrace):
    return [sp for sp in pt.spans if sp[0] != ROOT_SPAN]


def innermost_cover(gap: tuple[int, int], spans) -> str | None:
    """The span that names ``gap``: the innermost (shortest) of those
    covering at least half of it; else, where the spans together cover
    half, the one covering the most; else None."""
    s, e = gap
    overlap = [(min(e, se) - max(s, ss), se - ss, name)
               for name, ss, se in spans if min(e, se) > max(s, ss)]
    if not overlap:
        return None
    halves = [(dur, name) for ov, dur, name in overlap if 2 * ov >= e - s]
    if halves:
        return min(halves)[1]
    if 2 * covered(clip([(ss, se) for _, ss, se in spans], s, e)) >= e - s:
        return max(overlap)[2]
    return None


def name_gap(gap: tuple[int, int], pt: ProgramTrace, spans) -> str:
    """``device_trace.attribute``'s names, with an in-round gap that
    ``spans`` cover named by ``innermost_cover``."""
    s, e = gap
    if 2 * covered(clip(pt.compiles, s, e)) >= e - s:
        return "compile"
    mid = (s + e) // 2
    for i, (rs, re_) in enumerate(pt.rounds):
        if rs <= mid < re_:
            name = innermost_cover(gap, spans) or "host, unattributed"
            return f"round {i + 1}: {name}"
    return "harness, between rounds"


def _idle(pt: ProgramTrace) -> list[tuple[int, int]]:
    """Every device's idle intervals in the window."""
    lo, hi = pt.window
    out = []
    for ops in pt.ops:
        out += gaps(union(clip(ops, lo, hi)), lo, hi)
    return out


def idle_gaps(pt: ProgramTrace, top: int = 10) -> list[tuple[str, int]]:
    """The ``top`` longest idle gaps, longest first, each named."""
    spans = _fine_spans(pt)
    ranked = sorted(_idle(pt), key=lambda g: g[0] - g[1])[:top]
    return [(name_gap(g, pt, spans), g[1] - g[0]) for g in ranked]


def idle_attributed_share(pt: ProgramTrace) -> float | None:
    """Percent of the in-round idle time that a span finer than
    ``fedsdd.round`` covers; None where the rounds hold no idle time."""
    rounds = union(pt.rounds)
    cover = union((s, e) for _, s, e in _fine_spans(pt))
    idle = named = 0
    for s, e in _idle(pt):
        for part in clip(rounds, s, e):
            idle += part[1] - part[0]
            named += covered(clip(cover, *part))
    return 100.0 * named / idle if idle else None
