"""The benchmark's harness: its files found by name, its METG arithmetic,
its names, its refusal to run without a chip, and a whole run on the CPU
at a tiny size."""
import json
import os
import re
import shutil
import subprocess
import sys
import threading
import time

import jax
import numpy as np
import pytest

from chipbench import harness, metg, peaks, reference

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


# ------------------------------------------------------------ the contract
def test_benchmark_json_names_units_and_files():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    metrics = b["end_to_end"] + b["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for e in b["end_to_end"]:
        assert 0.01 <= e["bound"] <= 0.25 and e["source"] == "host_clock"
    assert "setup_s" in [e["name"] for e in b["end_to_end"]]
    cells = [w["name"] for w in b["workloads"]]
    assert len(set(cells)) == len(cells)
    for w in b["workloads"]:
        for key in ("name", "config", "traffic"):
            assert NAME.match(w[key]), w
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        cell = harness.load_cell(w["name"], REPO)
        assert cell.per_layer and len(cell.end_to_end) >= 2
        for m in cell.per_layer:
            harness.load_reader(m, REPO)
    for c in b["configs"]:
        assert NAME.match(c["name"]) and c["file"].startswith("chipbench/")
        with open(os.path.join(REPO, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"]
    layers = {m["layer"] for m in b["per_layer"]}
    assert all("\n" not in x and len(x) <= 200 for x in layers)


def test_new_cell_config_and_metric_are_found_by_name(tiny_root):
    root = tiny_root("xla-scan")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    cfg_path = os.path.join(root, "chipbench", "configs", "stencil-compute.json")
    with open(cfg_path) as f:
        cfg = json.load(f)
    cfg["name"] = "stencil-compute-b"
    with open(os.path.join(root, "chipbench", "configs",
                           "stencil-compute-b.json"), "w") as f:
        json.dump(cfg, f)
    b["configs"].append({"name": "stencil-compute-b", "source": "x",
                         "file": "chipbench/configs/stencil-compute-b.json",
                         "reduced": [], "why": "x"})
    b["workloads"].append({"name": "new-cell", "config": "stencil-compute-b",
                           "traffic": "t2", "chips": 1, "why": "x"})
    b["per_layer"].append({"name": "new_metric.fine", "unit": "us",
                           "better": "lower", "source": "device_trace",
                           "layer": "device", "moves": "metg_us",
                           "workloads": ["new-cell"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    with open(os.path.join(root, "chipbench", "cells", "new-cell.json"), "w") as f:
        json.dump({"config": "stencil-compute-b", "traffic": "t2", "chips": 1,
                   "backend": "xla-scan", "width": 8, "iterations": [2, 1],
                   "why": "x"}, f)
    with open(os.path.join(root, "chipbench", "metrics",
                           "new_metric.fine.py"), "w") as f:
        f.write('LAYER = "device"\nUNIT = "us"\nMOVES = "metg_us"\n\n\n'
                'def read(windows):\n    return 1.5\n')
    cell = harness.load_cell("new-cell", root)
    assert cell.config["name"] == "stencil-compute-b"
    assert cell.spec["iterations"] == [2, 1]
    names = [m["name"] for m in cell.per_layer]
    assert "new_metric.fine" in names
    reader = harness.load_reader(cell.per_layer[names.index("new_metric.fine")],
                                 root)
    assert reader.read({}) == 1.5
    assert "new_metric.fine" not in [
        m["name"] for m in harness.load_cell("tiny", root).per_layer]


def test_a_cell_file_that_disagrees_is_refused(tiny_root):
    root = tiny_root("xla-scan")
    path = os.path.join(root, "chipbench", "cells", "tiny.json")
    with open(path) as f:
        spec = json.load(f)
    spec["chips"] = 4
    with open(path, "w") as f:
        json.dump(spec, f)
    with pytest.raises(harness.CellError, match="chips"):
        harness.load_cell("tiny", root)


def test_peak_table_refuses_an_unknown_device():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks("TPU v99")


# ------------------------------------------------------- the copied METG
def synthetic_points(o, w, iters_list, num_tasks=256, flops_per_iter=2048.0):
    """wall = tasks * (overhead + work): the paper's overhead model."""
    return [metg.SweepPoint(it, num_tasks * (o + it * w), num_tasks,
                            num_tasks * it * flops_per_iter,
                            granularity=o + it * w)
            for it in iters_list]


@pytest.mark.parametrize("case", ["analytic", "never", "threshold", "noise"])
def test_copied_metg_reproduces_the_program_cases(case):
    o, w = 1e-5, 1e-8
    sweep = metg.geometric_iterations(1 << 20, 1, 2.0)
    if case == "analytic":
        res = metg.compute_metg(synthetic_points(o, w, sweep), threshold=0.5)
        assert res.metg == pytest.approx(2 * o, rel=0.15)
    elif case == "never":
        pts = synthetic_points(1e-3, 1e-9, [1024, 256, 64, 16, 4, 1])
        assert metg.compute_metg(pts, 0.5, peak_rate=2048 / 1e-9 * 2).metg is None
    elif case == "threshold":
        pts = synthetic_points(o, w, sweep)
        assert (metg.compute_metg(pts, threshold=0.9).metg
                > metg.compute_metg(pts, threshold=0.5).metg)
    else:
        pts = synthetic_points(o, w, metg.geometric_iterations(1 << 18, 1, 2.0))
        pts[3].wall_time *= 1.12
        pts[3].granularity *= 1.12
        assert metg.compute_metg(pts).metg == pytest.approx(2 * o, rel=0.35)


def test_copied_metg_agrees_with_the_program_copy():
    from repro.bench import metg as program

    o, w = 3e-6, 2e-9
    its = metg.geometric_iterations(4096, 1, 2.0)
    assert its == program.geometric_iterations(4096, 1, 2.0)
    mine = metg.compute_metg(synthetic_points(o, w, its))
    theirs = program.compute_metg([
        program.SweepPoint(p.iterations, p.wall_time, p.num_tasks,
                           p.useful_work, granularity=p.granularity)
        for p in synthetic_points(o, w, its)])
    assert mine.metg == theirs.metg and mine.peak_rate == theirs.peak_rate


# ------------------------------------------------------------ reference
@pytest.mark.parametrize("pattern,params,offsets", [
    ("stencil", {}, [-1, 0, 1]),
    ("nearest", {"radix": 5}, [-2, -1, 0, 1, 2]),
])
@pytest.mark.parametrize("iterations", [1, 5, 64])
def test_reference_agrees_with_the_program_oracle(pattern, params, offsets,
                                                  iterations):
    from repro.core import execute_reference, make_graph

    g = make_graph(12, 9, pattern, "compute", iterations=iterations, **params)
    want = execute_reference(g)
    got = reference.final_payload(12, 9, offsets, iterations, g.payload_elems)
    np.testing.assert_array_equal(got, want)


def test_compare_counts_mismatches_and_the_kernel_gap():
    ref = reference.final_payload(8, 6, [-1, 0, 1], 7, 5)
    bad = ref.copy()
    bad[2, 3] += 1
    off = ref.copy()
    off[:, 4] += 1e-3
    r = reference.compare([ref, bad, off, ref[:4]], ref, kernel_limit=1e-4)
    assert r["runs"] == 4 and r["failed_runs"] == 3
    assert r["mismatches"] == 1 + ref.size
    assert r["kernel_abs_err"] == pytest.approx(1e-3, rel=1e-3)


# ---------------------------------------------------------- running it
def run_py(cwd, *args):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "chipbench", "run.py"), *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def no_result(proc):
    lines = proc.stdout.strip().splitlines()
    return not lines or '"metrics"' not in lines[-1]


def test_run_py_without_a_chip_exits_nonzero_with_no_result():
    proc = run_py(REPO, "--workload", "stencil-compute.xla-scan.w128",
                  "--seed", "1", "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0 and no_result(proc)
    assert "no TPU" in proc.stderr


def test_run_py_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = run_py(str(tmp_path), "--workload", "stencil-compute.xla-scan.w128",
                  "--seed", "1", "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0 and no_result(proc)


def test_a_tiny_run_on_the_cpu_is_correct_and_reports_every_metric(tiny_root):
    root = tiny_root("xla-scan")
    t0 = time.perf_counter()
    logged = []
    r = harness.run_cell("tiny", 3_000_000_019, 0.6, False, jax.devices(), t0,
                         root=root, log=logged.append)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 3
    assert set(r["metrics"]) == {"metg_us", "coarse_gflops", "setup_s"}
    assert all(v["value"] > 0 for v in r["metrics"].values())
    assert list(r)[-1] == "checks"
    assert r["checks"]["mismatches"] == {"value": 0, "limit": 0}
    assert set(r["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert "compiles in the window: 0" in logged[0]


def test_the_seed_orders_the_points_and_changes_no_work(tiny_root):
    cell = harness.load_cell("tiny", tiny_root("xla-scan", iterations=(8, 4, 2, 1)))
    orders = []
    for seed in (7, 2**31 + 11, 7):
        seen = []
        pts = [harness.Point(k, (lambda k=k: seen.append(k) or [None]), 1, 1.0)
               for k in cell.spec["iterations"]]
        harness.window(pts, 0.02, seed)
        orders.append([k for i, k in enumerate(seen)
                       if i == 0 or seen[i - 1] != k])
        assert sorted(set(orders[-1])) == [1, 2, 4, 8]
        assert pts[0].runs >= 1 and pts[0].ahead_runs >= harness.AHEAD
        assert all(p.ahead_runs == 0 for p in pts[1:])
    assert orders[0] == orders[2]


def test_drive_ahead_keeps_a_run_queued_and_counts_all_of_its_time():
    """Two runs overlap, every output is kept, the share ends with its
    last run, and a run that raises ends the share with its error."""
    live, peak, lock = [0], [0], threading.Lock()

    def runner():
        with lock:
            live[0] += 1
            peak[0] = max(peak[0], live[0])
        time.sleep(0.01)
        with lock:
            live[0] -= 1
        return [np.zeros(1)]

    p = harness.Point(8, runner, 1, 1.0)
    t0 = time.perf_counter()
    harness.drive_ahead(p, 0.2)
    took = time.perf_counter() - t0
    assert peak[0] == harness.AHEAD and live[0] == 0
    assert p.ahead_runs == len(p.outputs) > harness.AHEAD
    assert p.ahead_seconds == pytest.approx(took, abs=5e-3)
    assert 0.18 < p.ahead_seconds < 0.25
    assert p.runs == 0 and p.times == []

    def broken():
        raise RuntimeError("no chip")

    with pytest.raises(RuntimeError, match="no chip"):
        harness.drive_ahead(harness.Point(8, broken, 1, 1.0), 0.05)


def test_end_to_end_arithmetic_counts_all_time_and_the_chips():
    """Per-task overhead o and body cost c per iteration: rate, efficiency
    and the 50% crossing from whole-window times, granularity x chips."""
    o, c, tasks, chips = 2e-6, 1e-7, 1000, 4
    pts = []
    for k in (64, 32, 16, 8, 4, 2, 1):
        p = harness.Point(k, None, tasks, tasks * 2048.0 * k)
        p.runs, p.seconds = 10, 10 * tasks * (o + c * k) / chips
        pts.append(p)
    # the coarsest point's queued share hides the overhead o
    pts[0].ahead_runs, pts[0].ahead_seconds = 20, 20 * tasks * c * 64 / chips
    e2e = harness.end_to_end(pts, chips, setup_s=3.5)
    assert e2e["setup_s"] == 3.5
    assert e2e["coarse_gflops"] == pytest.approx(
        2048.0 * 64 / (c * 64) * chips / 1e9)
    # efficiency k / (o + c k) over its best, at 64, crosses 50% near 12
    res = metg.compute_metg([metg.SweepPoint(
        p.iterations, p.run_s, tasks, p.flops,
        granularity=p.run_s * chips / tasks) for p in pts])
    assert e2e["metg_us"] == pytest.approx(res.metg * 1e6)
    assert (o + c * 8) * 1e6 < e2e["metg_us"] < (o + c * 16) * 1e6
