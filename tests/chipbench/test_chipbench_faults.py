"""``correct`` fails when it should: the control (the reference computed
in bfloat16 in the program's place) and faults planted in the timed
path underneath a whole run, at a size the CPU holds.  The harness's look
for a chip is skipped; on the CPU the fused kernel runs in interpret
mode, so its Mosaic check is stubbed and the numbers alone must catch
each fault."""
import json
import os
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl

from chipbench import harness, reference

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HEIGHT = 16
WIDTH = 8


def run(root, seed=5_000_000_029):
    return harness.run_cell("tiny", seed, 0.3, False, jax.devices(),
                            time.perf_counter(), root=root, log=lambda s: None)


# faults in the jitted backends' shared timestep (backends/body.py)
def scan_fault(kind, orig):
    def timestep(graph, t, prev, mat, iters, cols=None, dynamic=False):
        new = orig(graph, t, prev, mat, iters, cols=cols, dynamic=dynamic)
        if kind == "state_unchanged":
            return jnp.where(t == 3, prev[:new.shape[0]], new)
        if kind == "half_left_out":
            return new.at[WIDTH // 2:].set(prev[WIDTH // 2:new.shape[0]])
        if kind == "answer_altered":
            return new.at[0, 3].add(jnp.where(t == HEIGHT - 1, 1.0, 0.0))
        raise ValueError(kind)
    return timestep


# the same faults inside the fused kernel (backends/megakernel.py)
def fused_fault(kind, orig):
    def kernel(*refs, **kw):
        out_ref = refs[4]
        t = pl.program_id(1)
        prev = out_ref[...]
        orig(*refs, **kw)
        if kind == "state_unchanged":
            @pl.when(t == 3)
            def _():
                out_ref[...] = prev
        elif kind == "half_left_out":
            @pl.when(t > 0)
            def _():
                out_ref[WIDTH // 2:, :] = prev[WIDTH // 2:, :]
        elif kind == "answer_altered":
            @pl.when(t == HEIGHT - 1)
            def _():
                out_ref[0:1, 3:4] = out_ref[0:1, 3:4] + 1.0
    return kernel


FAULTS = ["state_unchanged", "half_left_out", "answer_altered"]


@pytest.mark.parametrize("backend", ["xla-scan", "pallas-fused"])
def test_a_sound_run_is_correct(tiny_root, monkeypatch, backend):
    monkeypatch.setattr(harness, "mosaic_faults", lambda cell, be: 0)
    r = run(tiny_root(backend, width=WIDTH, height=HEIGHT))
    assert r["correct"] is True, r["checks"]


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("backend", ["xla-scan", "pallas-fused"])
def test_a_planted_fault_is_not_correct(tiny_root, monkeypatch, backend, fault):
    from repro.backends import body, megakernel

    monkeypatch.setattr(harness, "mosaic_faults", lambda cell, be: 0)
    if backend == "xla-scan":
        monkeypatch.setattr(body, "timestep", scan_fault(fault, body.timestep))
    else:
        monkeypatch.setattr(megakernel, "_fused_kernel",
                            fused_fault(fault, megakernel._fused_kernel))
    r = run(tiny_root(backend, width=WIDTH, height=HEIGHT))
    assert r["correct"] is False
    assert r["failed"] == r["attempted"]
    assert r["checks"]["mismatches"]["value"] > 0


@pytest.mark.parametrize("fault", [False, True])
def test_a_coarse_point_cut_short_is_not_correct(tiny_root, monkeypatch, fault):
    """Past 16 iterations the kernel's result no longer shows how many ran:
    only the runs' times can tell that the coarsest point stopped at the
    next point's count."""
    from chipbench import control

    build = harness.build

    def cut_build(cell, devices):
        backend, points = build(cell, devices)
        if fault:
            control.coarse_cut(points)
        return backend, points

    monkeypatch.setattr(harness, "build", cut_build)
    r = run(tiny_root("xla-scan", width=WIDTH, height=HEIGHT,
                      iterations=(1024, 256, 1)))
    assert r["checks"]["mismatches"]["value"] == 0
    ratio = r["checks"]["iteration_time_ratio"]
    assert (ratio["value"] <= ratio["limit"]) is (not fault), ratio
    assert r["correct"] is (not fault)


def test_the_iteration_check_is_left_out_where_the_output_shows_the_count():
    cell = harness.Cell("c", {"limits": {"kernel_abs_err": 1e-4,
                                         "iteration_time_ratio": 1.4}},
                        {}, 1, [], [])
    pts = [harness.Point(k, None, 1, 1.0, times=[k * 1e-3]) for k in (8, 4)]
    assert harness.iteration_time_ratio(cell, pts) is None
    pts = [harness.Point(k, None, 1, 1.0, times=[1.0]) for k in (32, 16)]
    assert harness.iteration_time_ratio(cell, pts) == pytest.approx(2.0)


def test_interpret_mode_is_not_the_mosaic_kernel(tiny_root):
    r = run(tiny_root("pallas-fused", width=WIDTH, height=HEIGHT,
                      iterations=(2, 1)))
    assert r["checks"]["not_mosaic"] == {"value": 2, "limit": 0}
    assert r["correct"] is False


@pytest.mark.parametrize("config,offsets", [
    ("stencil-compute", [-1, 0, 1]),
    ("nearest5-compute", [-2, -1, 0, 1, 2]),
])
def test_the_bfloat16_control_is_not_correct(tiny_root, config, offsets):
    """The control at the cells' own height and widths, put in the place of
    the program's outputs for every sweep point."""
    from chipbench import control

    root = tiny_root("xla-scan", width=128, height=1000,
                     iterations=(64, 8, 1), config=config)
    cell = harness.load_cell("tiny", root)
    pts = [harness.Point(k, None, 128 * 1000, 1.0,
                         outputs=[reference.final_payload(
                             128, 1000, offsets, k, 5)] * 2)
           for k in cell.spec["iterations"]]
    runs, failed, checks = harness.check(cell, pts, None)
    assert harness.passed(checks) and failed == 0
    control.control_outputs(cell, pts)
    runs, failed, checks = harness.check(cell, pts, None)
    assert not harness.passed(checks) and failed == runs == 6
    assert checks["mismatches"]["value"] > 0


EXCHANGE = textwrap.dedent("""
    import json, sys, time
    sys.path[:0] = [{repo!r}, {src!r}]
    import jax, jax.numpy as jnp
    from chipbench import harness
    from repro.dist import collectives
    if {fault!r}:
        orig = collectives.CommPlan.exchange
        def exchange(self, payload):
            ctx = orig(self, payload)
            h = self.halo
            return ctx.at[:h].set(0.0).at[-h:].set(0.0)
        collectives.CommPlan.exchange = exchange
    r = harness.run_cell("tiny", 11, 0.3, False, jax.devices()[:4],
                         time.perf_counter(), root={root!r}, log=lambda s: None)
    print(json.dumps({{"correct": r["correct"], "checks": r["checks"]}}))
""")


@pytest.mark.parametrize("fault", [False, True])
def test_the_exchange_between_chips_left_out_is_not_correct(tiny_root, fault):
    """Four virtual CPU devices in a child process: the halo exchange of
    ``shardmap-csp`` returns zeros in place of the neighbours' rows."""
    root = tiny_root("shardmap-csp", width=16, height=HEIGHT, chips=4,
                     config="nearest5-compute")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = EXCHANGE.format(repo=REPO, src=os.path.join(REPO, "src"),
                           root=root, fault=fault)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    r = json.loads(proc.stdout.strip().splitlines()[-1])
    assert r["correct"] is (not fault), r
