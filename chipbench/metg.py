"""METG(e): the smallest task granularity that keeps efficiency e.

The benchmark's own copy of the paper's §IV arithmetic, so that no change
to the program can move the yardstick.  Granularity is wall time x cores /
tasks; efficiency is a point's rate over the best rate of the sweep; the
crossing of the threshold is interpolated on a log granularity axis, as
the paper's Figures 2-3 construct it.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import List, Optional, Sequence


@dataclass
class SweepPoint:
    iterations: int
    wall_time: float  # seconds per graph run
    num_tasks: int
    useful_work: float  # FLOPs per graph run
    granularity: float = 0.0  # seconds per task (x cores)
    rate: float = 0.0  # work / second
    efficiency: float = 0.0  # rate / peak_rate


@dataclass
class METGResult:
    metg: Optional[float]  # seconds; None if the curve never crosses
    threshold: float
    peak_rate: float
    points: List[SweepPoint] = field(default_factory=list)


def observed_peak(points: Sequence[SweepPoint]) -> float:
    """The 100%-efficiency baseline: the best rate in the sweep."""
    return max((p.rate for p in points), default=0.0)


def efficiency_curve(points: Sequence[SweepPoint],
                     peak_rate: Optional[float] = None) -> List[SweepPoint]:
    """Copies of ``points`` with ``rate`` and ``efficiency`` filled in."""
    pts = [SweepPoint(**vars(p)) for p in points]
    for p in pts:
        p.rate = p.useful_work / p.wall_time if p.wall_time > 0 else 0.0
    if peak_rate is None:
        peak_rate = observed_peak(pts)
    for p in pts:
        p.efficiency = p.rate / peak_rate if peak_rate > 0 else 0.0
    return pts


def compute_metg(points: Sequence[SweepPoint], threshold: float = 0.5,
                 peak_rate: Optional[float] = None) -> METGResult:
    """Build the efficiency curve and find the threshold crossing.

    The smallest granularity still at or above the threshold; where the
    next smaller point dips below, the crossing is log-interpolated
    between the two (robust to small non-monotonicity from noise).
    """
    pts = efficiency_curve(points, peak_rate=peak_rate)
    if peak_rate is None:
        peak_rate = observed_peak(pts)
    if peak_rate <= 0:
        return METGResult(None, threshold, 0.0, pts)
    ordered = sorted(pts, key=lambda p: -p.granularity)
    above = [p for p in ordered if p.efficiency >= threshold]
    if not above:
        return METGResult(None, threshold, peak_rate, pts)
    prev = above[-1]
    metg: Optional[float] = prev.granularity
    below = [p for p in ordered
             if p.granularity < prev.granularity and p.efficiency < threshold]
    if below:
        p = below[0]
        if prev.efficiency > p.efficiency and p.granularity > 0:
            lo_g, hi_g = math.log(p.granularity), math.log(prev.granularity)
            frac = (threshold - p.efficiency) / (prev.efficiency - p.efficiency)
            metg = math.exp(lo_g + frac * (hi_g - lo_g))
    return METGResult(metg, threshold, peak_rate, pts)


def geometric_iterations(hi: int, lo: int = 1, factor: float = 2.0) -> List[int]:
    """Sweep schedule: hi, hi/f, ... down to lo (deduplicated)."""
    if not 1 <= lo <= hi:
        raise ValueError(f"need 1 <= lo <= hi, got lo={lo}, hi={hi}")
    out, x = [], float(hi)
    while x >= lo:
        v = max(lo, int(round(x)))
        if not out or v != out[-1]:
            out.append(v)
        x /= factor
    if out[-1] != lo:
        out.append(lo)
    return out
