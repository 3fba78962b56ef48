"""The benchmark's core: one run of one cell.

A cell (``cells/<workload>.json``) names a configuration
(``configs/<config>.json``), a backend spec, a width, its chips and the
iteration counts of its sweep.  A run:

1. set-up: builds the graph of every sweep point, gets its runner from
   ``get_backend(spec, devices=...).prepare([graph])`` (compiled, or
   loaded from the persistent cache) and runs it once;
2. window: gives each sweep point, and the coarsest point once more with
   a run queued behind the one on the chip (``drive_ahead``), an equal
   share of ``seconds``, in an order drawn from the seed, and calls the
   runner over and over, whole graph runs only, keeping every output;
3. with ``trace``, a short traced window at the finest and at the
   coarsest point, reduced by ``trace.py`` and read by the per-layer
   metrics (``metrics/<name>.py``);
4. once the chips' peak memory is read and the runners are freed,
   compares every kept output with the plain reference
   (``reference.py``), checks from the runs' times that the coarsest
   point ran all its iterations where its output cannot show it, and,
   for ``pallas-fused``, checks that the program is the compiled Mosaic
   kernel.

End-to-end metrics (tracing off): ``metg_us``, METG(50%) over the sweep;
``coarse_gflops``, the rate at the coarsest point with a run queued;
``setup_s``, process start to the first timed run.
"""
from __future__ import annotations

import gc
import glob
import importlib.util
import json
import os
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from . import metg, reference
from . import trace as T

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_SECONDS = 0.25  # each traced window, at least TRACE_MIN_RUNS runs
TRACE_MIN_RUNS = 2
AHEAD = 2  # graph runs in flight in the coarsest point's queued share


class CellError(ValueError):
    """A cell, configuration or metric file that does not fit."""


def read_json(path: str):
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    config: dict
    spec: dict
    chips: int
    end_to_end: List[dict]
    per_layer: List[dict]


def _by_name(entries: Sequence[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise CellError(f"BENCHMARK.json names no {what} {name!r}")


def _applies(metric: dict, cell: str, e2e_names: Sequence[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric["moves"] in e2e_names


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` as ``BENCHMARK.json`` and its files describe it."""
    bench = read_json(os.path.join(root, "BENCHMARK.json"))
    entry = _by_name(bench["workloads"], name, "workload")
    config = read_json(os.path.join(
        root, _by_name(bench["configs"], entry["config"], "config")["file"]))
    spec = read_json(os.path.join(root, "chipbench", "cells", name + ".json"))
    for key in ("config", "traffic", "chips"):
        if spec[key] != entry[key]:
            raise CellError(f"cells/{name}.json has {key}={spec[key]!r}, "
                            f"BENCHMARK.json {entry[key]!r}")
    e2e = bench["end_to_end"]
    e2e_names = [m["name"] for m in e2e]
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, name, e2e_names)]
    return Cell(name, config, spec, entry["chips"], e2e, per_layer)


def load_reader(metric: dict, root: str = ROOT):
    """The module ``metrics/<name>.py``: its ``read(windows)`` returns the
    metric or None, and its UNIT, LAYER and MOVES match ``metric``."""
    path = os.path.join(root, "chipbench", "metrics", metric["name"] + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + metric["name"].replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    for attr, key in (("UNIT", "unit"), ("LAYER", "layer"), ("MOVES", "moves")):
        if getattr(mod, attr) != metric[key]:
            raise CellError(f"metrics/{metric['name']}.py has {attr}="
                            f"{getattr(mod, attr)!r}, BENCHMARK.json "
                            f"{metric[key]!r}")
    return mod


# ------------------------------------------------------------ the sweep
def task_flops(config: dict, iterations: int) -> float:
    """Operations of one task: ``flops_per_element`` for each element of
    the kernel's tile, each iteration."""
    rows, lanes = config["tile"]
    return float(iterations * rows * lanes * config["flops_per_element"])


@dataclass
class Point:
    iterations: int
    runner: Optional[Callable]
    tasks: int
    flops: float  # per graph run
    outputs: List[np.ndarray] = field(default_factory=list)
    times: List[float] = field(default_factory=list)  # each run's seconds
    runs: int = 0
    seconds: float = 0.0
    ahead_runs: int = 0  # runs and seconds of ``drive_ahead``
    ahead_seconds: float = 0.0

    @property
    def run_s(self) -> float:
        return self.seconds / self.runs


def make_graph(cell: Cell, iterations: int):
    from repro.core import make_graph as mk

    c = cell.config
    return mk(cell.spec["width"], c["height"], c["pattern"], c["kernel"],
              iterations=iterations, output_bytes=c["payload_bytes"],
              **c["pattern_params"])


def build(cell: Cell, devices: Sequence):
    """The backend and one warmed ``Point`` per sweep iteration count."""
    from repro.backends import get_backend

    backend = get_backend(cell.spec["backend"], devices=list(devices))
    tasks = cell.spec["width"] * cell.config["height"]
    points = []
    for k in cell.spec["iterations"]:
        runner = backend.prepare([make_graph(cell, k)])
        runner()
        points.append(Point(k, runner, tasks, tasks * task_flops(cell.config, k)))
    return backend, points


def drive(point: Point, seconds: float, span: Optional[Callable] = None
          ) -> None:
    """Run ``point`` for about ``seconds``: whole runs only, at least
    one, and none that would be expected to end past the share."""
    t0 = time.perf_counter()
    n, elapsed = 0, 0.0
    while True:
        t_run = time.perf_counter()
        if span is None:
            out = point.runner()
        else:
            with span("chipbench.run"):
                out = point.runner()
        point.outputs.append(out[0])
        now = time.perf_counter()
        point.times.append(now - t_run)
        n += 1
        elapsed = now - t0
        if elapsed + elapsed / n > seconds:
            break
    point.runs += n
    point.seconds += elapsed


def drive_ahead(point: Point, seconds: float) -> None:
    """Run ``point`` for about ``seconds`` from ``AHEAD`` threads, so that
    a run is queued on the chip while the host waits for another: a host
    stall shorter than a run leaves the chip busy.  A thread starts no
    run that its last run's time says would end past the share; the
    clock is read once every thread has ended, so all the runs count over
    all the time."""
    t0 = time.perf_counter()
    end = t0 + seconds
    counts = [0] * AHEAD
    errors: List[BaseException] = []

    def worker(k: int) -> None:
        last = 0.0
        try:
            while counts[k] == 0 or time.perf_counter() + last <= end:
                t_run = time.perf_counter()
                point.outputs.append(point.runner()[0])
                last = time.perf_counter() - t_run
                counts[k] += 1
        except BaseException as e:  # re-raised below, once all have ended
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(k,)) for k in range(AHEAD)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    point.ahead_runs += sum(counts)
    point.ahead_seconds += time.perf_counter() - t0


def window(points: Sequence[Point], seconds: float, seed: int) -> None:
    """One share for each point, and one more for the coarsest point
    driven ahead, in an order drawn from the seed."""
    shares = len(points) + 1
    order = np.random.default_rng(seed % (1 << 64)).permutation(shares)
    for i in order:
        if i < len(points):
            drive(points[i], seconds / shares)
        else:
            drive_ahead(max(points, key=lambda p: p.iterations),
                        seconds / shares)


def end_to_end(points: Sequence[Point], chips: int, setup_s: float
               ) -> Dict[str, float]:
    pts = [metg.SweepPoint(p.iterations, p.run_s, p.tasks, p.flops,
                           granularity=p.run_s * chips / p.tasks)
           for p in points]
    res = metg.compute_metg(pts, threshold=0.5)
    coarse = max(points, key=lambda p: p.iterations)
    out = {"coarse_gflops": coarse.flops * coarse.ahead_runs
           / coarse.ahead_seconds / 1e9,
           "setup_s": setup_s}
    if res.metg is not None:
        out["metg_us"] = res.metg * 1e6
    return out


def describe(points: Sequence[Point], chips: int) -> List[str]:
    pts = metg.efficiency_curve(
        [metg.SweepPoint(p.iterations, p.run_s, p.tasks, p.flops)
         for p in points])
    top = max(points, key=lambda p: p.iterations)
    return [f"iterations {p.iterations}: {q.runs} runs in {q.seconds:.4f} s, "
            f"{p.wall_time * 1e3:.4f} ms/run ({min(q.times) * 1e3:.4f} to "
            f"{max(q.times) * 1e3:.4f}), {p.rate / 1e9:.3f} GFLOP/s, "
            f"efficiency {p.efficiency:.4f}, granularity "
            f"{p.wall_time * chips / p.num_tasks * 1e6:.5f} us"
            for p, q in zip(pts, points)] + [
        f"iterations {top.iterations}, {AHEAD} in flight: {top.ahead_runs} "
        f"runs in {top.ahead_seconds:.4f} s, "
        f"{top.flops * top.ahead_runs / top.ahead_seconds / 1e9:.3f} GFLOP/s"]


# ----------------------------------------------------------------- trace
@dataclass
class Traced:
    """What a per-layer metric reads: one traced window at one point."""

    point: Point
    runs: int
    height: int
    trace: T.Window


def traced_windows(cell: Cell, points: Sequence[Point], chip_ids: Sequence[int],
                   log: Callable[[str], None]) -> Dict[str, Traced]:
    """Trace the finest and the coarsest point, each in a profiler session
    of its own, and reduce each trace as soon as it is written.  Only the
    fine window's operations are read: at the coarsest point a run holds
    a million or more of them, and its busy time needs none."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    out = {}
    for label, p in (("fine", min(points, key=lambda p: p.iterations)),
                     ("coarse", max(points, key=lambda p: p.iterations))):
        before = p.runs
        t0 = time.perf_counter()
        with tempfile.TemporaryDirectory(prefix="chipbench-trace-") as d:
            with jax.profiler.trace(d, profiler_options=opts):
                with jax.profiler.TraceAnnotation(T.WINDOW_PREFIX + label):
                    drive(p, max(TRACE_SECONDS, TRACE_MIN_RUNS * p.run_s),
                          span=jax.profiler.TraceAnnotation)
            t1 = time.perf_counter()
            path, = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                              recursive=True)
            size = os.path.getsize(path)
            window = T.reduce_profile(T.load(path), chip_ids,
                                      ops=label == "fine")[label]
        out[label] = Traced(p, p.runs - before, cell.config["height"], window)
        log(f"trace {label}: {p.iterations} iterations, {p.runs - before} "
            f"runs; traced {t1 - t0:.3f} s, {size} bytes, reduced in "
            f"{time.perf_counter() - t1:.3f} s")
    return out


# ----------------------------------------------------------- correctness
def mosaic_faults(cell: Cell, backend) -> int:
    """For a ``pallas-fused`` cell, the sweep points whose program is not
    the compiled Mosaic kernel: the backend runs in interpret mode, or
    the lowering holds no ``tpu_custom_call``."""
    if not cell.spec["backend"].startswith("pallas-fused"):
        return 0
    bad = 0
    for k in cell.spec["iterations"]:
        text = backend.lowered_stablehlo([make_graph(cell, k)])
        bad += int(backend.interpret or "tpu_custom_call" not in text)
    return bad


def iteration_time_ratio(cell: Cell, points: Sequence[Point]
                         ) -> Optional[float]:
    """Time per iteration at the next coarser point over that at the
    coarsest, each from its fastest run; None where the reference tells
    the two points' results apart.

    The kernel's orbit settles by 16 iterations, so past that the output
    no longer shows how many ran.  A program that runs every iteration
    reads a little over 1 (the fixed cost of a run is spread over more
    iterations at the coarsest point); one whose coarsest point stops at
    the next point's count reads the ratio of the two counts, 2 for a
    sweep by halves.
    """
    if len(points) < 2:
        return None
    nxt, top = sorted(points, key=lambda p: p.iterations)[-2:]
    blind = abs(reference.kernel_result(top.iterations)
                - reference.kernel_result(nxt.iterations))
    if blind > cell.config["limits"]["kernel_abs_err"]:
        return None
    if not (nxt.times and top.times):
        return float("inf")
    return (min(nxt.times) / nxt.iterations) / (min(top.times) / top.iterations)


def check(cell: Cell, points: Sequence[Point], backend):
    """Every kept output against the float32 reference, and the coarsest
    point's time per iteration where its output cannot show it.  Returns
    the runs checked, the runs whose output failed, and the numbers
    compared, each with its limit."""
    c = cell.config
    limit = c["limits"]["kernel_abs_err"]
    payload = max(5, c["payload_bytes"] // 4)
    runs = failed = mismatches = 0
    worst = 0.0
    for p in points:
        ref = reference.final_payload(cell.spec["width"], c["height"],
                                      c["dep_offsets"], p.iterations, payload)
        r = reference.compare(p.outputs, ref, limit)
        runs += r["runs"]
        failed += r["failed_runs"]
        mismatches += r["mismatches"]
        worst = max(worst, r["kernel_abs_err"])
    checks = {"mismatches": {"value": mismatches, "limit": 0},
              "kernel_abs_err": {"value": worst, "limit": limit}}
    ratio = iteration_time_ratio(cell, points)
    if ratio is not None:
        checks["iteration_time_ratio"] = {
            "value": ratio, "limit": c["limits"]["iteration_time_ratio"]}
    if cell.spec["backend"].startswith("pallas-fused"):
        checks["not_mosaic"] = {"value": mosaic_faults(cell, backend),
                                "limit": 0}
    return runs, failed, checks


def passed(checks: Dict[str, Dict[str, float]]) -> bool:
    """Every number compared is within its limit (an upper one)."""
    return all(v["value"] <= v["limit"] for v in checks.values())


# ------------------------------------------------------------------- run
def device_facts(devices: Sequence) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             devices: Sequence, t_start: float, root: str = ROOT,
             log: Callable[[str], None] = lambda s: print(s, file=sys.stderr,
                                                          flush=True)) -> dict:
    """One run of cell ``name`` on ``devices``; returns the result line.

    ``t_start`` is the process's start on ``time.perf_counter``'s clock.
    """
    import jax

    cell = load_cell(name, root)
    readers = [(m, load_reader(m, root)) for m in cell.per_layer]
    devices = list(devices)[:cell.chips]
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    with jax.default_device(devices[0]):
        backend, points = build(cell, devices)
        setup_s = time.perf_counter() - t_start
        n_compiles = len(compiles)
        window(points, seconds, seed)
        log(f"{cell.name}: set-up {setup_s:.3f} s; window {seconds} s over "
            f"{len(points)} points; compiles in the window: "
            f"{len(compiles) - n_compiles}")
        for line in describe(points, cell.chips):
            log("  " + line)
        e2e = end_to_end(points, cell.chips, setup_s)
        traced = (traced_windows(cell, points, [d.id for d in devices], log)
                  if trace else None)
        device = device_facts(devices)
    for p in points:
        p.runner = None
    gc.collect()
    runs, failed, checks = check(cell, points, backend)
    result = {"correct": passed(checks) and runs > 0, "attempted": runs,
              "failed": failed}
    if traced is None:
        missing = [m["name"] for m in cell.end_to_end if m["name"] not in e2e]
        if missing:
            raise RuntimeError(f"no {missing}: the sweep's efficiency never "
                               f"falls below 50%; extend it to finer points")
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    else:
        metrics = {}
        for m, mod in readers:
            v = mod.read(traced)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        wins = list(traced.values())
        device["busy_s"] = sum(t.trace.busy_s for t in wins)
        device["window_s"] = sum(t.trace.window_s for t in wins)
        ops, idle = {}, {}
        for t in wins:
            for k, v in t.trace.op_ns.items():
                ops[k] = ops.get(k, 0.0) + v
            for k, v in t.trace.gap_ns.items():
                idle[k] = idle.get(k, 0.0) + v
        result["breakdown"] = {"device_ops": T.top(ops), "idle_gaps": T.top(idle)}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = checks
    return result
