"""metg_scaling: the weak-scaling family (paper §V-D/E).

Covers the three layers separately so failures localize:

* the ``SyntheticTimer`` rank-count model (closed-form assertions — the
  charged wall time is a pure function of ``(graph, ranks, spec)``),
* the ``kind="metg_scaling"`` artifact schema incl. corruption
  rejection, and the ``compare`` gate branch,
* the subprocess rank launcher end to end (ranks {1, 2} on the
  synthetic timer — deterministic, so exact cross-process equality).
"""
from __future__ import annotations

import copy
import json
import os

import pytest

from repro.bench import SyntheticTimer, validate_artifact
from repro.bench.compare import compare_artifacts
from repro.bench.scaling import (SCALING_BACKENDS, ScalingSpec, rank_env,
                                 run_rank_cell, run_scaling,
                                 scaling_artifact, write_scaling_json)
from repro.bench.timers import backend_comm_hints
from repro.core.graph import TaskGraph

SYNTH = {"name": "synthetic", "config": {}}


# ----------------------------------------------------- rank model (timers)
def test_backend_comm_hints_resolve_by_name_only():
    assert backend_comm_hints("shardmap-csp") == (False, False)
    assert backend_comm_hints("shardmap-csp[comm=onesided]") == (True, False)
    assert backend_comm_hints("shardmap-csp[comm_overlap=true]") == (False,
                                                                     True)
    # malformed specs fall back to blocking two-sided, never raise
    assert backend_comm_hints("no [such] backend!!") == (False, False)


def test_ranked_model_trivial_pattern_is_block_makespan():
    """No dependencies -> no comm; uniform tasks split into equal static
    blocks, so the wall time is height * (width/ranks) * per-task cost."""
    t = SyntheticTimer(ranks=4, seconds_per_byte=1e-9,
                      seconds_per_rendezvous=1e-6)
    g = TaskGraph(width=8, height=5, pattern="trivial")
    per_task = (t.overhead_per_task
                + g.task_iterations(0, 0) * t.seconds_per_iteration)
    expected = 5 * 2 * per_task  # 2 columns per rank
    assert t.measure("shardmap-csp", [g]) == pytest.approx(expected)


def test_ranked_model_charges_only_cross_rank_deps():
    """Stencil deps at a rank boundary pay the message cost; the same
    graph at ranks=1 pays nothing (everything is rank-local)."""
    g = TaskGraph(width=8, height=4, pattern="stencil", output_bytes=1024)
    kw = dict(seconds_per_byte=1e-9, seconds_per_rendezvous=5e-6)
    t1 = SyntheticTimer(ranks=1, **kw)
    t2 = SyntheticTimer(ranks=2, **kw)
    per_task = (t1.overhead_per_task
                + g.task_iterations(0, 0) * t1.seconds_per_iteration)
    # ranks=1: pure compute, sequential over all 32 tasks
    assert t1.measure("shardmap-csp", [g]) == pytest.approx(32 * per_task)
    # ranks=2: boundary columns 3<->4 exchange across the cut; stencil
    # (radius 1) crosses it twice per timestep except t=0 (no deps)
    import numpy as np

    from repro.core.schedule import static_owners

    owners = static_owners(8, 2)
    cross = int((g.dependence_matrices()
                 & (owners[None, :, None] != owners[None, None, :])).sum())
    assert cross == 3 * 2
    per_dep = kw["seconds_per_byte"] * 1024 + kw["seconds_per_rendezvous"]
    expected = 4 * 4 * per_task + cross * per_dep  # blocking: compute + comm
    assert t2.measure("shardmap-csp", [g]) == pytest.approx(expected)
    # onesided: no rendezvous surcharge, and comm overlaps compute
    t2o = SyntheticTimer(ranks=2, **kw)
    comm = cross * kw["seconds_per_byte"] * 1024
    assert t2o.measure("shardmap-csp[comm=onesided]", [g]) == pytest.approx(
        max(4 * 4 * per_task, comm))


def test_rank_model_off_by_default():
    """ranks=0 (the default) must leave every existing family's charged
    model untouched."""
    g = TaskGraph(width=4, height=4, pattern="trivial")
    assert (SyntheticTimer().measure("xla-scan", [g])
            == SyntheticTimer(ranks=0).measure("xla-scan", [g]))


# ------------------------------------------------------------ spec checks
def test_scaling_spec_validation():
    with pytest.raises(ValueError, match="ascending"):
        ScalingSpec(name="s", ranks=(1, 4, 2))
    with pytest.raises(ValueError, match="include 1"):
        ScalingSpec(name="s", ranks=(2, 4))
    with pytest.raises(ValueError, match="non-empty"):
        ScalingSpec(name="s", ranks=())
    with pytest.raises(ValueError, match="needs a name"):
        ScalingSpec(name="")
    spec = ScalingSpec(name="s", ranks=(1, 2))
    sc = spec.scenario_for(2, smoke=True)
    assert sc.width == 2 * spec.width_per_rank
    assert sc.name == "s.r2"
    with pytest.raises(ValueError, match="not in"):
        spec.scenario_for(8)


def test_rank_env_pins_device_count_and_strips_inherited():
    base = {"JAX_NUM_CPU_DEVICES": "8",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8 "
                         "--xla_dump_to=/tmp/d",
            "PYTHONPATH": "/elsewhere"}
    env = rank_env(4, base)
    # exactly one device-count knob, set to the child's rank count
    pinned = [env.get("JAX_NUM_CPU_DEVICES"),
              *[f.split("=")[1] for f in env.get("XLA_FLAGS", "").split()
                if f.startswith("--xla_force_host_platform_device_count")]]
    assert [p for p in pinned if p is not None] == ["4"]
    # unrelated XLA flags survive; the checkout's src leads PYTHONPATH
    assert "--xla_dump_to=/tmp/d" in env.get("XLA_FLAGS", "")
    first = env["PYTHONPATH"].split(os.pathsep)[0]
    assert os.path.isdir(os.path.join(first, "repro"))
    assert "/elsewhere" in env["PYTHONPATH"].split(os.pathsep)


# ------------------------------------------------- artifact schema + gate
def _cells(spec, ranks=(1, 2)):
    return [run_rank_cell(spec, n, True, SYNTH) for n in ranks]


@pytest.fixture(scope="module")
def scaling_doc():
    spec = ScalingSpec(name="metg_scaling.t", backend="shardmap-csp",
                       ranks=(1, 2))
    return scaling_artifact(spec, _cells(spec), smoke=True)


def test_scaling_artifact_schema(scaling_doc):
    doc = validate_artifact(scaling_doc)
    assert doc["kind"] == "metg_scaling"
    assert doc["scenario"]["ranks"] == [1, 2]
    r1, r2 = doc["cells"]
    assert r1["weak_efficiency"] == pytest.approx(1.0)
    assert 0.0 < r2["weak_efficiency"] <= 1.0
    assert r2["width"] == 2 * doc["scenario"]["width_per_rank"]
    # contour: every cell sweeps the same iteration grid
    assert ([p["iterations"] for p in r1["points"]]
            == [p["iterations"] for p in r2["points"]])


@pytest.mark.parametrize("mutate,match", [
    (lambda d: d["cells"].pop(), "cover"),
    (lambda d: d["cells"][0].__setitem__("ranks", 3), "cover"),
    (lambda d: d["scenario"].__setitem__("ranks", [2, 1]), "ascending"),
    (lambda d: d["cells"][1].__setitem__("width", 5), "width"),
    (lambda d: d["cells"][0].__setitem__("elapsed_s", float("nan")),
     "elapsed_s"),  # NaN fails _typed's finiteness guard
    (lambda d: d["cells"][0].__setitem__("elapsed_s", True),
     "elapsed_s"),  # bool <: int is rejected for numeric fields
    (lambda d: d["cells"][0]["points"][0].pop("weak_efficiency"),
     "weak_efficiency"),
    (lambda d: d.__setitem__("cells", []), "cover|cells"),
])
def test_scaling_artifact_rejects_corruption(scaling_doc, mutate, match):
    doc = copy.deepcopy(scaling_doc)
    mutate(doc)
    with pytest.raises(ValueError, match=match):
        validate_artifact(doc)


def test_compare_scaling_gate(scaling_doc):
    base = copy.deepcopy(scaling_doc)
    # identical -> ok, with the headline efficiency note
    res = compare_artifacts(base, copy.deepcopy(base))
    assert res.ok and res.note.startswith("eff@r2=")
    # per-rank elapsed regression trips
    cur = copy.deepcopy(base)
    cur["cells"][1]["elapsed_s"] *= 2.0
    for p in cur["cells"][1]["points"]:
        p["wall_time_s"] *= 2.0
    res = compare_artifacts(base, cur)
    assert not res.ok and any("ranks=2 elapsed" in r for r in res.regressions)
    # weak-efficiency drop trips even at equal elapsed threshold margins
    cur = copy.deepcopy(base)
    cur["cells"][1]["weak_efficiency"] *= 0.5
    res = compare_artifacts(base, cur)
    assert any("weak_efficiency" in r for r in res.regressions)
    # a shrunk rank list is an identity change (different experiment) —
    # caught before any numeric diff
    cur = copy.deepcopy(base)
    cur["cells"] = cur["cells"][:1]
    cur["scenario"]["ranks"] = [1]
    res = compare_artifacts(base, cur)
    assert any("scenario.ranks changed" in r for r in res.regressions)
    # a rank cell vanished with the scenario unchanged (a corrupt or
    # hand-edited doc slipping past identity) is a per-cell regression
    cur = copy.deepcopy(base)
    cur["cells"] = cur["cells"][:1]
    res = compare_artifacts(base, cur)
    assert any("ranks=2 missing" in r for r in res.regressions)
    # timer mismatch refuses to compare numbers
    cur = copy.deepcopy(base)
    cur["timer"] = "wallclock"
    res = compare_artifacts(base, cur)
    assert any("timer changed" in r for r in res.regressions)


# --------------------------------------------------- launcher integration
def test_run_scaling_subprocess_launcher(tmp_path):
    """End to end through real child processes: deterministic timer, so
    the subprocess cells equal in-process ``run_rank_cell`` exactly."""
    from repro.bench.scaling import _timer_payload, scaling_timer

    spec = ScalingSpec(name="metg_scaling.launch", backend="shardmap-csp",
                       ranks=(1, 2))
    result = run_scaling(spec, timer=SyntheticTimer(), smoke=True)
    payload = _timer_payload(scaling_timer(SyntheticTimer()))
    cells = [run_rank_cell(spec, n, True, payload) for n in (1, 2)]
    in_process = scaling_artifact(spec, cells, smoke=True)
    assert result.doc["cells"] == in_process["cells"]
    path = write_scaling_json(result, str(tmp_path))
    assert os.path.basename(path) == "BENCH_metg_scaling.launch.json"
    with open(path) as f:
        assert validate_artifact(json.load(f))["kind"] == "metg_scaling"


def test_bench_module_backends_filter(tmp_path, capsys):
    from benchmarks.run import main

    with pytest.raises(SystemExit) as exc:
        main(["--smoke", "--timer", "synthetic",
              "--only", "bench_metg_scaling",
              "--backends", "xla-scan",
              "--artifacts", str(tmp_path)])
    assert exc.value.code == 1
    assert "matches none" in capsys.readouterr().out


def test_scaling_backends_are_multirank_only():
    """The family must sweep exactly the backends whose CommPlan paths
    span ranks; a single-device backend in the list measures nothing."""
    assert set(SCALING_BACKENDS) == {
        "shardmap-csp", "shardmap-csp[comm=onesided]",
        "shardmap-pipeline", "shardmap-pipeline[comm=onesided]", "auto"}


def test_run_scaling_in_process_over_given_devices():
    """With ``devices`` (the TPU path) every rank count runs in this
    process on a backend built over ``devices[:n]`` — no child."""
    import jax

    from repro.bench.scenario import SweepControls
    from repro.bench.sweep import run_scenario

    spec = ScalingSpec(name="metg_scaling.inproc", backend="shardmap-csp",
                       ranks=(1,), height=4,
                       sweep=SweepControls(schedule=(16, 1), repeats=1))
    result = run_scaling(spec, smoke=True, devices=jax.devices()[:1],
                         python="/nonexistent/python")
    (cell,) = result.cells
    assert cell["ranks"] == cell["devices"] == 1
    assert result.doc["timer"] == "wallclock"
    with pytest.raises(ValueError, match="give your timer its own devices"):
        run_scenario(spec.scenario_for(1, smoke=True),
                     timer=SyntheticTimer(), devices=jax.devices()[:1])
    with pytest.raises(ValueError, match="need 2 devices"):
        run_scaling(ScalingSpec(name="s", ranks=(1, 2)), smoke=True,
                    devices=jax.devices()[:1])
