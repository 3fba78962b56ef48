"""Reduction of a profiler trace to device busy time, idle gaps, time per
operation and collective time, within the benchmark's host spans.

The harness wraps each traced stretch in a host span named
``chipbench.window.<label>`` (``jax.profiler.TraceAnnotation``); the
profiler puts host spans and device events on one clock.  Within each
window and on each chip:

* busy time is the union of the program executions on the chip's
  ``XLA Modules`` line;
* an idle gap is a stretch of the window in which no program runs.  It is
  put down to the innermost host span on the window's thread that covers
  the gap's middle: what the host was doing while the chip waited;
* with ``ops``, the ``XLA Ops`` line gives each operation's self time
  (its duration less that of the operations nested in it, as a while
  loop holds its body), and the collectives' share of it.  A long window
  at many iterations holds millions of operations, so the caller reads
  them only where it needs them.

Numbers are averaged over the chips.
"""
from __future__ import annotations

import functools
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_PREFIX = "chipbench.window."
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|all-to-all|reduce-scatter|collective-permute|"
    r"collective-broadcast|ragged-all-to-all|send|recv)")
CUSTOM_TARGET = re.compile(r'custom_call_target="([^"]+)"')

Interval = Tuple[float, float]


@dataclass
class Window:
    """One traced window, averaged over ``chips``."""

    label: str
    start_ns: float
    end_ns: float
    chips: int = 0
    busy_ns: float = 0.0
    collective_ns: float = 0.0
    op_ns: Dict[str, float] = field(default_factory=dict)
    gap_ns: Dict[str, float] = field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9

    @property
    def busy_s(self) -> float:
        return self.busy_ns * 1e-9


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, disjoint union of ``intervals``."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: Sequence[Interval], start: float, end: float) -> List[Interval]:
    """The stretches of [start, end] that ``busy`` (disjoint, sorted)
    leaves free."""
    out, cur = [], start
    for s, e in busy:
        if s > cur:
            out.append((cur, min(s, end)))
        cur = max(cur, e)
        if cur >= end:
            break
    if cur < end:
        out.append((cur, end))
    return [(s, e) for s, e in out if e > s]


def _clip(s: float, e: float, lo: float, hi: float) -> Optional[Interval]:
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def attribute(spans: Sequence[Tuple[float, float, str]],
              times: Sequence[float]) -> List[str]:
    """For each of ``times`` (ascending), the name of the innermost span
    that covers it.  The spans are of one thread, so properly nested:
    one pass with a stack of open spans."""
    ordered = sorted(spans, key=lambda sp: (sp[0], -sp[1]))
    out, stack, k = [], [], 0
    for t in times:
        while k < len(ordered) and ordered[k][0] <= t:
            while stack and stack[-1][1] < ordered[k][0]:
                stack.pop()
            stack.append(ordered[k])
            k += 1
        while stack and stack[-1][1] < t:
            stack.pop()
        out.append(stack[-1][2] if stack else "no host span")
    return out


def _events(line):
    for ev in line.events:
        yield ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns)


@functools.lru_cache(maxsize=4096)
def op_name(text: str) -> str:
    """``%fusion.8 = u32[128]{...} fusion(...)`` -> ``fusion.8``; a custom
    call keeps its target (``program.1 tpu_custom_call``)."""
    name = text.split(" = ", 1)[0].lstrip("%")
    m = CUSTOM_TARGET.search(text)
    return f"{name} {m.group(1)}" if m else name


def self_times(events: Sequence[Tuple[str, float, float]]) -> Dict[str, float]:
    """Self time per name of properly nested (name, start, end) events."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[List] = []  # [name, start, end, time of children]

    def close():
        name, s, e, inner = stack.pop()
        out[name] += max(e - s - inner, 0.0)

    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            close()
        if stack:
            stack[-1][3] += e - s
        stack.append([name, s, e, 0.0])
    while stack:
        close()
    return out


def host_windows(profile) -> Tuple[Dict[str, Interval],
                                   List[Tuple[float, float, str]]]:
    """The ``chipbench.window.*`` spans, and every span of the host
    thread that holds them."""
    windows: Dict[str, Interval] = {}
    spans: List[Tuple[float, float, str]] = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = list(_events(line))
            mine = {n[len(WINDOW_PREFIX):]: (s, e) for n, s, e in evs
                    if n.startswith(WINDOW_PREFIX)}
            if mine:
                windows.update(mine)
                spans.extend((s, e, n) for n, s, e in evs
                             if not n.startswith(WINDOW_PREFIX))
    return windows, spans


def device_lines(profile, name: str) -> Dict[int, object]:
    """Per chip id, its line called ``name``."""
    out = {}
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == name:
                    out[int(m.group(1))] = line
    return out


def _in_window(line, lo: float, hi: float) -> List[Tuple[str, float, float]]:
    out = []
    for name, s, e in _events(line):
        iv = _clip(s, e, lo, hi)
        if iv is not None:
            out.append((name, iv[0], iv[1]))
    return out


def reduce_profile(profile, chips: Sequence[int], ops: bool = False
                   ) -> Dict[str, Window]:
    """Reduce a loaded profile (``jax.profiler.ProfileData``) to one
    ``Window`` per ``chipbench.window.<label>`` span, over the chip ids
    ``chips``; with ``ops``, also time per operation."""

    windows, spans = host_windows(profile)
    modules = device_lines(profile, MODULE_LINE)
    op_lines = device_lines(profile, OP_LINE) if ops else {}
    missing = [c for c in chips
               if c not in modules or (ops and c not in op_lines)]
    if missing:
        raise ValueError(f"trace has no device lines for chips {missing}; "
                         f"found {sorted(modules)}")
    out = {}
    n = max(len(chips), 1)
    for label, (lo, hi) in windows.items():
        w = Window(label, lo, hi, chips=len(chips))
        op_ns: Dict[str, float] = defaultdict(float)
        gap_ns: Dict[str, float] = defaultdict(float)
        for c in chips:
            busy = union([(s, e) for _, s, e in _in_window(modules[c], lo, hi)])
            w.busy_ns += sum(e - s for s, e in busy) / n
            free = gaps(busy, lo, hi)
            names = attribute(spans, [(s + e) / 2 for s, e in free])
            for (s, e), name in zip(free, names):
                gap_ns[name] += (e - s) / n
            if ops:
                evs = [(op_name(t), s, e)
                       for t, s, e in _in_window(op_lines[c], lo, hi)]
                for name, v in self_times(evs).items():
                    op_ns[name] += v / n
                    if COLLECTIVE.match(name):
                        w.collective_ns += v / n
        w.op_ns, w.gap_ns = dict(op_ns), dict(gap_ns)
        out[label] = w
    return out


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def top(table: Dict[str, float], n: int = 10) -> List[List]:
    """The ``n`` largest entries as [name, seconds], largest first."""
    items = sorted(table.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in items]
