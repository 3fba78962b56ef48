"""Weak-scaling study (paper §V-D/E): METG efficiency as ranks grow.

The one Task Bench headline the single-device families cannot reproduce
is the scaling study: *fixed work per rank*, rank count swept, and the
efficiency-vs-granularity contour compressing against the overhead floor
as ranks (and therefore communication) grow.  This module is that family:

``ScalingSpec``
    One weak-scaling cell series: a backend, a per-rank problem shape
    (``width_per_rank`` columns per rank — the graph at ``n`` ranks is
    ``n`` times wider), and the rank sweep (default ``{1, 2, 4, 8}``).

``run_scaling``
    The rank launcher.  On a TPU every rank count runs in the one process
    that holds the chips, over a sub-mesh of ``jax.devices()[:n]``: a chip
    belongs to one process at a time, so a child could not reach it.  On
    the CPU, JAX fixes its device count at process start, so each rank
    count is measured in a *relaunched subprocess* with
    ``JAX_NUM_CPU_DEVICES=n``.  The child (``python -m
    repro.bench.scaling``) runs the ordinary ``run_scenario`` sweep for its
    rank count and prints one JSON cell.  Either way the parent assembles
    the ``kind="metg_scaling"`` artifact: per-rank elapsed, weak-scaling
    efficiency ``T(1)/T(n)`` (ideal 1.0 — work per rank is constant), and
    the per-granularity contour.

Determinism: under the ``SyntheticTimer`` the child charges the
rank-count model (``SyntheticTimer.ranks``, a pure function of
``(graph, ranks, spec string)``), so the committed
``BENCH_metg_scaling.*`` baselines are machine-independent and the CI
``--baseline`` gate is noise-free; under the wall clock the child
really builds the backend's ``CommPlan`` over ``n`` devices.
"""
from __future__ import annotations

import dataclasses
import json
import os
import re
import subprocess
import sys
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from .scenario import ScenarioSpec, SweepControls
from .studies import _guarded_ratio
from .sweep import run_scenario
from .timers import SyntheticTimer, Timer, timer_config

RANKS: Tuple[int, ...] = (1, 2, 4, 8)

# the backends whose CommPlan paths are actually multi-rank (xla-scan /
# xla-static / host-dynamic execute on one device regardless of the
# runtime's device count, so a rank sweep over them measures nothing)
SCALING_BACKENDS: Tuple[str, ...] = (
    "shardmap-csp",
    "shardmap-csp[comm=onesided]",
    "shardmap-pipeline",
    "shardmap-pipeline[comm=onesided]",
    "auto",
)

WIDTH_PER_RANK = 4
# largest-first, spanning coarse (compute-bound, eff ~ 1) down to the
# overhead floor; the smoke resolution keeps the sub-64 points so even CI
# baselines have a 3-point contour
SCALING_SCHEDULE: Tuple[int, ...] = (4096, 256, 16, 1)
# a mid-size payload so the synthetic model's cross-rank comm term is
# visible against the compute term inside the rank sweep
SCALING_OUTPUT_BYTES = 4096
SCALING_SECONDS_PER_BYTE = 4e-9
SCALING_SECONDS_PER_RENDEZVOUS = 2e-6


@dataclass(frozen=True)
class ScalingSpec:
    """One weak-scaling series: fixed work per rank, swept rank count."""

    name: str
    backend: str = "shardmap-csp"
    pattern: str = "stencil"
    kernel: str = "compute"
    width_per_rank: int = WIDTH_PER_RANK
    height: int = 16
    output_bytes: int = SCALING_OUTPUT_BYTES
    ranks: Tuple[int, ...] = RANKS
    sweep: SweepControls = field(
        default_factory=lambda: SweepControls(schedule=SCALING_SCHEDULE,
                                              repeats=3))

    def __post_init__(self):
        if not self.name:
            raise ValueError("scaling scenario needs a name (artifact key)")
        if self.width_per_rank < 1:
            raise ValueError("width_per_rank must be >= 1")
        if not self.ranks or any(int(n) < 1 for n in self.ranks):
            raise ValueError("ranks must be a non-empty list of counts >= 1")
        if list(self.ranks) != sorted(set(int(n) for n in self.ranks)):
            raise ValueError(
                f"ranks must be strictly ascending, got {self.ranks}")
        if self.ranks[0] != 1:
            raise ValueError(
                "ranks must include 1 (the weak-scaling efficiency "
                "reference T(1) every other rank normalizes against)")

    @property
    def slug(self) -> str:
        return re.sub(r"[^A-Za-z0-9_.-]+", "-", self.name)

    def scenario_for(self, nranks: int, smoke: bool = False) -> ScenarioSpec:
        """The per-rank scenario: ``nranks`` times wider, same work/rank."""
        if nranks not in self.ranks:
            raise ValueError(f"rank count {nranks} not in {self.ranks}")
        return ScenarioSpec(
            name=f"{self.name}.r{nranks}",
            backend=self.backend,
            pattern=self.pattern,
            kernel=self.kernel,
            width=self.width_per_rank * nranks,
            height=self.height,
            output_bytes=self.output_bytes,
            sweep=self.sweep,
        ).with_smoke(smoke)


def scaling_timer(timer: Optional[Timer]) -> Optional[Timer]:
    """Specialize a ``SyntheticTimer`` with the scaling-study comm rates.

    The per-rank ``ranks`` knob is applied by the *child* (it knows its
    rank count); other timers pass through unchanged — the study is then
    a real multi-device measurement.
    """
    if not isinstance(timer, SyntheticTimer):
        return timer
    return dataclasses.replace(
        timer,
        seconds_per_byte=SCALING_SECONDS_PER_BYTE,
        seconds_per_rendezvous=SCALING_SECONDS_PER_RENDEZVOUS)


# ------------------------------------------------------ subprocess launch

def rank_env(nranks: int, base: Optional[Dict[str, str]] = None,
             ) -> Dict[str, str]:
    """The child environment for an ``nranks``-device relaunch.

    Strips any inherited device-count forcing first (the CI multi-rank
    step exports ``JAX_NUM_CPU_DEVICES=8``; the child must see *its*
    rank count, not the parent's, and JAX rejects the XLA flag beside
    the variable), keeps unrelated ``XLA_FLAGS``, and prepends this
    checkout's ``src`` so ``python -m repro.bench.scaling`` resolves the
    same code the parent runs.
    """
    env = dict(os.environ if base is None else base)
    env["JAX_NUM_CPU_DEVICES"] = str(nranks)
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    if flags:
        env["XLA_FLAGS"] = " ".join(flags)
    else:
        env.pop("XLA_FLAGS", None)
    src = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    parts = [src] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                     if p and p != src]
    env["PYTHONPATH"] = os.pathsep.join(parts)
    return env


def _timer_payload(timer: Optional[Timer]) -> Optional[Dict]:
    """Serialize the parent's timer for the child relaunch."""
    if timer is None:
        return None
    if isinstance(timer, SyntheticTimer):
        return {"name": "synthetic", "config": timer_config(timer)}
    if timer.name == "wallclock":
        # the child rebuilds the default wall clock from the sweep
        # controls (exactly what a serial run_scenario does)
        return None
    raise ValueError(
        f"metg_scaling cannot relaunch under timer {timer.name!r}; "
        f"use the synthetic fake clock or the wall clock")


def _child_timer(payload: Optional[Dict], nranks: int) -> Optional[Timer]:
    if payload is None:
        return None
    cfg = dict(payload.get("config", {}))
    cfg["ranks"] = nranks
    return SyntheticTimer(**cfg)


def run_rank_cell(spec: ScalingSpec, nranks: int, smoke: bool,
                  timer_payload: Optional[Dict],
                  devices: Optional[Sequence] = None) -> Dict:
    """Measure one (spec, rank count) cell — the child's whole job, or,
    with ``devices`` (the cell's ``nranks`` devices), one in-process cell."""
    timer = _child_timer(timer_payload, nranks)
    sc = spec.scenario_for(nranks, smoke=smoke)
    result = run_scenario(sc, timer=timer,
                          devices=devices if timer is None else None)
    if timer_payload is not None:
        ndevices = nranks  # the synthetic rank model
    elif devices is not None:
        ndevices = len(devices)
    else:
        import jax

        ndevices = len(jax.devices())
    return {
        "ranks": nranks,
        "width": result.spec.width,
        "devices": ndevices,
        "timer": result.timer,
        "timer_config": dict(result.timer_config),
        "sweep": _sweep_doc(result.spec.sweep),
        "points": [
            {
                "iterations": p.iterations,
                "num_tasks": p.num_tasks,
                "wall_time_s": p.wall_time,
                "granularity_s": p.granularity,
                "efficiency": p.efficiency,
            }
            for p in sorted(result.points, key=lambda p: -p.iterations)
        ],
    }


def _sweep_doc(sweep: SweepControls) -> Dict:
    doc = dataclasses.asdict(sweep)
    doc["schedule"] = (list(sweep.schedule)
                       if sweep.schedule is not None else None)
    return doc


def _launch_cell(spec: ScalingSpec, nranks: int, smoke: bool,
                 timer_payload: Optional[Dict],
                 python: str) -> Dict:
    payload = json.dumps({
        "spec": {**dataclasses.asdict(spec),
                 "ranks": list(spec.ranks),
                 "sweep": _sweep_doc(spec.sweep)},
        "nranks": nranks,
        "smoke": smoke,
        "timer": timer_payload,
    })
    proc = subprocess.run(
        [python, "-m", "repro.bench.scaling"],
        input=payload, capture_output=True, text=True,
        env=rank_env(nranks))
    if proc.returncode != 0:
        tail = "\n".join(proc.stderr.strip().splitlines()[-8:])
        raise RuntimeError(
            f"metg_scaling child for {spec.name!r} at ranks={nranks} "
            f"exited {proc.returncode}:\n{tail}")
    try:
        return json.loads(proc.stdout)
    except json.JSONDecodeError as e:
        raise RuntimeError(
            f"metg_scaling child for {spec.name!r} at ranks={nranks} "
            f"printed unparseable output ({e}): {proc.stdout[:200]!r}")


def scaling_artifact(spec: ScalingSpec, cells: List[Dict],
                     smoke: bool) -> Dict:
    """Assemble the ``kind="metg_scaling"`` artifact from rank cells."""
    from .artifact import SCHEMA_VERSION, _canonical_backend

    cells = sorted(cells, key=lambda c: c["ranks"])
    base = {p["iterations"]: p["wall_time_s"]
            for p in cells[0]["points"]} if cells else {}
    out_cells = []
    for c in cells:
        points = []
        for p in c["points"]:
            ref = base.get(p["iterations"])
            points.append({**p, "weak_efficiency": _guarded_ratio(
                ref if ref is not None else float("nan"),
                p["wall_time_s"])})
        head = points[0] if points else {}
        out_cells.append({
            "ranks": c["ranks"],
            "width": c["width"],
            "devices": c["devices"],
            "elapsed_s": head.get("wall_time_s", 0.0),
            "granularity_s": head.get("granularity_s", 0.0),
            "weak_efficiency": head.get("weak_efficiency", 0.0),
            "points": points,
        })
    ref_sweep = cells[0]["sweep"] if cells else _sweep_doc(
        spec.scenario_for(spec.ranks[0], smoke=smoke).resolved().sweep)
    return {
        "schema": SCHEMA_VERSION,
        "kind": "metg_scaling",
        "scenario": {
            "name": spec.name,
            "backend": _canonical_backend(spec.backend),
            "pattern": spec.pattern,
            "kernel": spec.kernel,
            "width_per_rank": spec.width_per_rank,
            "height": spec.height,
            "output_bytes": spec.output_bytes,
            "ranks": [c["ranks"] for c in cells] or list(spec.ranks),
            "sweep": ref_sweep,
        },
        "timer": cells[0]["timer"] if cells else "wallclock",
        "timer_config": cells[0]["timer_config"] if cells else {},
        "cells": out_cells,
    }


@dataclass
class ScalingResult:
    """One assembled weak-scaling series, ready for the artifact writer."""

    spec: ScalingSpec
    doc: Dict

    @property
    def cells(self) -> List[Dict]:
        return self.doc["cells"]

    def cell(self, nranks: int) -> Dict:
        for c in self.cells:
            if c["ranks"] == nranks:
                return c
        raise KeyError(f"no cell for ranks={nranks}")


def run_scaling(spec: ScalingSpec, timer: Optional[Timer] = None,
                smoke: bool = False,
                python: str = sys.executable,
                devices: Optional[Sequence] = None) -> ScalingResult:
    """Measure one weak-scaling series.

    With ``devices``, and by default on a TPU (over ``jax.devices()``),
    every rank count ``n`` runs in this process over ``devices[:n]``;
    otherwise each runs in a relaunched child pinned to ``n`` CPU devices.
    """
    import jax

    from .artifact import validate_artifact

    payload = _timer_payload(scaling_timer(timer))
    if devices is None and jax.default_backend() == "tpu":
        devices = jax.devices()
    if devices is not None:
        if len(devices) < spec.ranks[-1]:
            raise ValueError(f"{spec.name}: ranks {spec.ranks} need "
                             f"{spec.ranks[-1]} devices, have {len(devices)}")
        cells = [run_rank_cell(spec, n, smoke, payload, devices=devices[:n])
                 for n in spec.ranks]
    else:
        cells = [_launch_cell(spec, n, smoke, payload, python)
                 for n in spec.ranks]
    timers = {c["timer"] for c in cells}
    if len(timers) != 1:
        raise RuntimeError(
            f"metg_scaling children disagreed on the timer: {sorted(timers)}")
    doc = validate_artifact(scaling_artifact(spec, cells, smoke))
    return ScalingResult(spec=spec, doc=doc)


def write_scaling_json(result: ScalingResult, outdir: str) -> str:
    """Write ``BENCH_<scenario>.json`` (validated); returns the path."""
    from .artifact import write_artifact_doc

    return write_artifact_doc(result.doc, result.spec.slug, outdir)


def _child_main() -> None:
    req = json.load(sys.stdin)
    sp = dict(req["spec"])
    sp["ranks"] = tuple(sp["ranks"])
    sweep = dict(sp["sweep"])
    sweep["schedule"] = (tuple(sweep["schedule"])
                         if sweep["schedule"] is not None else None)
    sp["sweep"] = SweepControls(**sweep)
    spec = ScalingSpec(**sp)
    cell = run_rank_cell(spec, int(req["nranks"]), bool(req["smoke"]),
                         req["timer"])
    json.dump(cell, sys.stdout)
    sys.stdout.write("\n")


if __name__ == "__main__":
    _child_main()
